"""gen_lag_p99_ms: 99th percentile of how late the load generator called
``submit`` after a request's due time (host clock), so that a starved
generator is not read as a fast service.  Moves latency_p99_ms."""
import numpy as np


def read(run):
    if run.loop != "open" or not run.lag_ms.size:
        return None
    return float(np.percentile(run.lag_ms, 99))
