"""join_roofline_share: the least time HBM needs to move the bytes the
join must read, over the join's device time, in percent.

The bytes count the work needed, not the work done: for each request,
the int32 ranks and s-values of both label rows at their true lengths
(``8 * (len(u) + len(v))``, lengths from the served snapshot), not the
padded width or the bucket.  A batch carries the window's mean number
of requests (answered / batches) and the window's mean bytes per
request; its time is the traced mean device time of one join run.  The
compute term is left out: no published integer peak of the VPU exists.
"""
import tracefile


def read(run):
    t = run.trace
    if t is None or not t.join_runs or not run.batches:
        return None
    hbm = tracefile.peak(run.device_kind)["hbm_bytes_per_s"]
    bytes_per_batch = run.answered / run.batches * run.join_bytes_per_request
    seconds_per_batch = t.join_s / t.join_runs
    return 100.0 * bytes_per_batch / hbm / seconds_per_batch
