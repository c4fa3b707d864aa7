"""latency_p50_ms: median, over every request of the window, of the time
from its due time to its answer (host clock)."""
import numpy as np


def read(run):
    if not run.latency_ms.size:
        return None
    return float(np.percentile(run.latency_ms, 50))
