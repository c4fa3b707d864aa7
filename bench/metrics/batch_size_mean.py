"""batch_size_mean: requests answered per dispatched batch over the
window, from the service's own counters (``ServiceStats.answered`` and
``.batches``; a batch is one kind's padded device call)."""


def read(run):
    if not run.batches:
        return None
    return run.answered / run.batches
