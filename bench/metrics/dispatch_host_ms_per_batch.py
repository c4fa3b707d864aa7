"""dispatch_host_ms_per_batch: host milliseconds the service thread
spends per dispatched kind group around the device call: the wall time
of its ``repro.serve.take``, ``refresh``, ``prepare`` and ``resolve``
spans, over its ``repro.serve.join`` spans (one per group; what
``ServiceStats.batches`` counts).  Inside the traced stretch; a span
across its edge counts by its share inside.  None for a program without
these spans."""
import spans


def read(run):
    att = spans.of_run(run)
    if att is None or not att.batches:
        return None
    host = sum(att.wall_s(n) for n in spans.HOST_STAGES)
    return host / att.batches * 1e3
