"""Serial vs sharded HL-index construction across graph sizes.

The tentpole claim of the sharded builder (repro.core.hlindex.
build_sharded) is tracked as numbers, not prose: on each swept graph the
serial ``build_fast`` and the sharded builder (shared neighbor-index
CSR, per-device component shards, forked workers) run on identical
input, labels are asserted **byte-identical**, sampled answers are
pinned to the independent ``mst-oracle``, and the wall times land in
``BENCH_construction.json`` at the repo root — the accumulating record
the CI smoke job regenerates at tiny sizes.

On a multi-device host (XLA_FLAGS=--xla_force_host_platform_device_
count=N) the neighbor overlaps are computed on the mesh and the worker
count follows the device count, so the sweep doubles as the ≥2-device
scaling record.

  PYTHONPATH=src python -m benchmarks.bench_construction            # sweep
  PYTHONPATH=src python -m benchmarks.bench_construction --quick    # CI
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np
from repro.compile_cache import enable_compile_cache


def component_graph(components: int, n_per: int, m_per: int,
                    seed: int = 0):
    """``components`` disjoint random blocks — the multi-component regime
    sharded construction partitions (one block ≈ one line-graph
    component, up to random fragmentation inside a block)."""
    from repro.core import from_edge_lists, random_hypergraph

    edges = []
    offset = 0
    for c in range(components):
        block = random_hypergraph(n_per, m_per, seed=seed * 1000 + c)
        for e in range(block.m):
            edges.append((block.edge(e) + offset).tolist())
        offset += n_per
    return from_edge_lists(edges, n=offset)


def bench_size(components: int, n_per: int, m_per: int, *, mesh, workers,
               n_queries: int, reps: int, seed: int = 0) -> dict:
    from repro.core import MSTOracle, build_fast, build_sharded, mr_query

    h = component_graph(components, n_per, m_per, seed=seed)
    num_shards = max(int(mesh.devices.size), workers, 1)

    # one timing loop per variant (not interleaved) so each row's min
    # sees the same allocator/cache state across its reps
    serial_s, sharded_s, pool_s = [], [], []
    serial_idx = sharded_idx = None
    for _ in range(reps):
        t0 = time.perf_counter()
        serial_idx = build_fast(h)
        serial_s.append(time.perf_counter() - t0)
    for _ in range(reps):
        # the engine's default sharded path (workers unspecified): the
        # auto work gate engages the fork pool only past
        # _POOL_MIN_NEIGHBOR_ENTRIES, so what this row measures is
        # exactly what `build_engine(h, "hl-index", mesh=mesh)` would
        # run — the headline row
        t0 = time.perf_counter()
        sharded_idx = build_sharded(h, mesh=mesh, num_shards=num_shards)
        sharded_s.append(time.perf_counter() - t0)
    for _ in range(reps):
        # the fork-pool variant — pays off once per-shard traversals
        # outweigh the pool's fixed start/pickle cost and the host has
        # cores to spare (recorded either way so the trade-off is
        # visible in the JSON)
        t0 = time.perf_counter()
        pool_idx = build_sharded(h, mesh=mesh, num_shards=num_shards,
                                 workers=workers)
        pool_s.append(time.perf_counter() - t0)

    # byte-identity on every variant's final output
    for other in (sharded_idx, pool_idx):
        assert np.array_equal(serial_idx.rank, other.rank)
        for u in range(h.n):
            assert (serial_idx.labels_rank[u].tobytes()
                    == other.labels_rank[u].tobytes())
            assert (serial_idx.labels_s[u].tobytes()
                    == other.labels_s[u].tobytes())

    # sampled answers pinned to the independent oracle
    oracle = MSTOracle(h)
    rng = np.random.default_rng(seed)
    us = rng.integers(0, h.n, n_queries)
    vs = rng.integers(0, h.n, n_queries)
    for u, v in zip(us, vs):
        want = oracle.mr(int(u), int(v))
        assert mr_query(sharded_idx, int(u), int(v)) == want, (u, v)

    serial_best = min(serial_s)
    sharded_best = min(sharded_s)
    return {
        "components": components,
        "n": int(h.n),
        "m": int(h.m),
        "nnz": int(h.nnz),
        "labels": int(serial_idx.num_labels),
        "shards": int(sharded_idx.stats["shards"]),
        "workers": workers,
        "serial_s": serial_best,
        "sharded_s": sharded_best,
        "sharded_pool_s": min(pool_s),
        "speedup": serial_best / max(sharded_best, 1e-12),
        "pool_speedup": serial_best / max(min(pool_s), 1e-12),
        "answers_checked": int(n_queries),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny sizes for the CI smoke job")
    ap.add_argument("--reps", type=int, default=None)
    ap.add_argument("--n-queries", type=int, default=50)
    ap.add_argument("--workers", type=int, default=None,
                    help="shard worker processes (default: device count)")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_construction.json"))
    args = ap.parse_args()
    enable_compile_cache()

    import jax
    from repro.core.distributed import default_line_graph_mesh

    mesh = default_line_graph_mesh()
    devices = int(jax.device_count())
    cpus = os.cpu_count() or 1
    workers = (args.workers if args.workers is not None
               else max(min(devices, cpus), 2))
    if args.quick:
        sizes = [(4, 40, 30), (4, 80, 60)]
        reps = args.reps or 1
    else:
        sizes = [(4, 60, 50), (8, 150, 500), (8, 300, 900), (8, 300, 1400)]
        reps = args.reps or 3

    results = [bench_size(c, n, m, mesh=mesh, workers=workers,
                          n_queries=args.n_queries, reps=reps)
               for c, n, m in sizes]
    for row in results:
        print(f"construction m={row['m']} n={row['n']} "
              f"({row['components']} blocks, {row['shards']} shards): "
              f"serial {row['serial_s']:.3f}s vs sharded "
              f"{row['sharded_s']:.3f}s -> {row['speedup']:.2f}x "
              f"(pool x{row['workers']}: {row['sharded_pool_s']:.3f}s -> "
              f"{row['pool_speedup']:.2f}x; {row['answers_checked']} "
              f"answers oracle-checked, labels byte-identical)")
    doc = {
        "devices": devices,
        "cpus": cpus,
        "mesh_shape": {k: int(v) for k, v in
                       zip(mesh.axis_names,
                           np.asarray(mesh.devices).shape)},
        "workers": workers,
        "reps": reps,
        "note": ("build_sharded (shared NeighborCSR + per-device "
                 "component shards + reconciled merge) vs serial "
                 "build_fast on identical graphs; labels asserted "
                 "byte-identical and sampled answers asserted equal to "
                 "mst-oracle on every swept size.  `sharded_s` is the "
                 "engine's default path — workers unspecified, so the "
                 "auto gate engages the fork pool only past "
                 "_POOL_MIN_NEIGHBOR_ENTRIES neighbor entries (at the "
                 "swept sizes here it resolves inline); "
                 "`sharded_pool_s` forces forked workers, whose fixed "
                 "start+pickle cost only amortizes once per-shard "
                 "traversals run long enough — on few-core hosts the "
                 "default row is the honest one."),
        "results": results,
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")

    largest = results[-1]
    if largest["speedup"] <= 1.0:
        msg = (f"sharded build not faster at the largest size: "
               f"{largest['speedup']:.2f}x")
        if args.quick:
            print(f"WARNING: {msg} (quick mode: sizes too small to "
                  f"amortize the pool)")
        elif devices >= 2:
            raise SystemExit(f"FAIL: {msg} on a {devices}-device mesh")
        else:
            print(f"WARNING: {msg} (single-device host)")


if __name__ == "__main__":
    main()
