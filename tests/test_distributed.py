"""Multi-device tests (subprocess with forced host device count): the
sharded closures against the dense closure."""
from util_subproc import run_with_devices


def test_sharded_closures_match_dense():
    out = run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import random_hypergraph, mr_matrix, distinct_thresholds
from repro.core.distributed import (sharded_maxmin_closure,
                                    sharded_threshold_closure_mr)
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh((2, 2), ("data", "model"))
h = random_hypergraph(30, 26, seed=3)
w = h.line_graph(np.int32).astype(np.float32)
oracle = mr_matrix(h).astype(np.float32)
for sched in ("allgather", "ring"):
    got = np.asarray(sharded_maxmin_closure(w, mesh, schedule=sched))
    assert np.array_equal(got, oracle), sched
mesh3 = make_test_mesh((1, 2, 2), ("pod", "data", "model"))
thr = distinct_thresholds(w)
got = np.asarray(sharded_threshold_closure_mr(w, thr, mesh3))
assert np.array_equal(got, oracle)
print("OK")
""")
    assert "OK" in out

