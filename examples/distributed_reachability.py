import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# ^ must precede jax import: this example simulates an 8-device slice.
"""Distributed reachability: 2-D block-sharded semiring closures under
jax.shard_map with explicit collectives (DESIGN.md §2).

  PYTHONPATH=src python examples/distributed_reachability.py
"""
import time

import jax
import numpy as np

from repro.api import build_engine, plan_backend, random_hypergraph
from repro.core import distinct_thresholds, mr_matrix
from repro.core.distributed import (sharded_maxmin_closure,
                                    sharded_threshold_closure_mr,
                                    collective_bytes_of, sharded_maxmin_round,
                                    pad_for_mesh)
from repro.launch.mesh import make_test_mesh


def main():
    print("devices:", jax.device_count())
    mesh = make_test_mesh((2, 2), ("data", "model"))
    mesh3 = make_test_mesh((2, 2, 2), ("pod", "data", "model"))

    h = random_hypergraph(400, 600, min_size=2, max_size=6, seed=1)
    w = h.line_graph(np.int32).astype(np.float32)
    print(f"hypergraph: n={h.n} m={h.m}; line graph {w.shape}")

    # the single-device squaring closure is the reference: its W* is
    # exactly what the sharded closures must reproduce
    dense = mr_matrix(h).astype(np.float32)

    for sched in ("allgather", "ring"):
        t0 = time.perf_counter()
        got = np.asarray(sharded_maxmin_closure(w, mesh, schedule=sched))
        dt = time.perf_counter() - t0
        ok = np.array_equal(got, dense)
        print(f"maxmin closure [{sched:9s}] on 2x2 mesh: {dt:.2f}s  "
              f"correct={ok}")

    thr = distinct_thresholds(w)
    t0 = time.perf_counter()
    got = np.asarray(sharded_threshold_closure_mr(w, thr, mesh3))
    dt = time.perf_counter() - t0
    print(f"threshold closure (S={thr.size} over pod axis) on 2x2x2: "
          f"{dt:.2f}s  correct={np.array_equal(got, dense)}")

    # the "sharded" backend: the same closures behind the unified engine
    # API — computed once at build, served off a mesh-sharded snapshot
    rng = np.random.default_rng(0)
    us, vs = rng.integers(0, h.n, 256), rng.integers(0, h.n, 256)
    hl = build_engine(h, backend="hl-index")
    want = hl.mr_batch(us, vs).astype(np.int64)
    for sched in ("allgather", "ring"):
        eng = build_engine(h, backend="sharded", mesh=mesh, schedule=sched)
        ok = np.array_equal(np.asarray(eng.mr_batch(us, vs)).astype(np.int64),
                            want)
        print(f"sharded engine [{sched:9s}] == hl-index on 256 vertex "
              f"queries: {ok}")
    # the planner routes to "sharded" when a multi-device mesh is passed
    # and the closure exceeds the per-device budget
    print("auto planner with mesh + tight budget picks:",
          plan_backend(h, mesh=mesh, device_budget_bytes=0))

    # what goes over the wire per round
    from jax.sharding import NamedSharding, PartitionSpec as P
    wp = pad_for_mesh(w, mesh)
    rf = jax.jit(sharded_maxmin_round(mesh))
    lowered = rf.lower(jax.ShapeDtypeStruct(
        wp.shape, np.float32, sharding=NamedSharding(mesh, P("data", "model"))))
    info = collective_bytes_of(lowered.compile().as_text())
    print("per-round collective bytes (per device):", info["bytes"])


if __name__ == "__main__":
    main()
