"""join_ms_per_batch: device milliseconds of the join programs
(``batched_mr``; ``_gather_rows`` and ``label_join`` on the kernel path)
in the traced stretch, per run of them: one run answers one batch."""


def read(run):
    if run.trace is None or not run.trace.join_runs:
        return None
    return run.trace.join_s / run.trace.join_runs * 1e3
