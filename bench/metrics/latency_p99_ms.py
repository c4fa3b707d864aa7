"""latency_p99_ms: 99th percentile, over every request of the window,
of the time from its due time to its answer (host clock).  A request
that got no answer counts as infinitely late."""
import numpy as np


def read(run):
    if not run.latency_ms.size:
        return None
    return float(np.percentile(run.latency_ms, 99))
