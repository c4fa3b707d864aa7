"""Request-based serving vs per-call queries, plus snapshot-refresh cost
after scoped updates.

Two claims, tracked as numbers in ``BENCH_serving.json``:

1. **Admission micro-batching** — the same mixed 10k-query workload
   (MR + s-reach, mixed s) served request-by-request through
   ``eng.mr`` / ``eng.s_reach`` vs submitted to a
   ``ReachabilityService`` and coalesced into fused padded device
   batches.  The headline row uses the ``sharded`` backend — the
   production serving path, where every per-call query pays a full
   device dispatch and micro-batching is the designed fix (>= 5x
   asserted).  An ``hl-index`` row rides along for honesty: the paper's
   host merge-join answers in single-digit microseconds, so on a CPU
   host a Python admission queue cannot beat it — the service's win
   there is the snapshot lifecycle, not raw throughput.
   Every service answer is asserted equal to the independent
   ``mst-oracle`` reference.
2. **Snapshot caching across updates** — after a scoped ``update()``
   on a multi-component graph, the service's snapshot refresh
   re-derives only the touched label rows (counted via
   ``ServiceStats.rows_rederived`` / ``rows_full``), and answers still
   match the oracle.

Timed passes run against pre-warmed bucket shapes (steady-state
serving; the whole point of power-of-two bucketing is that compilation
is paid once per bucket, not per batch).

  PYTHONPATH=src python -m benchmarks.bench_serving            # full
  PYTHONPATH=src python -m benchmarks.bench_serving --quick    # CI
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np
from repro.compile_cache import enable_compile_cache


def _mixed_workload(h, rng, q):
    from repro.api import MRRequest, SReachRequest

    us = rng.integers(0, h.n, q)
    vs = rng.integers(0, h.n, q)
    is_mr = rng.random(q) < 0.5
    svals = rng.integers(1, 5, q)
    reqs = [MRRequest(int(u), int(v)) if k
            else SReachRequest(int(u), int(v), int(s))
            for u, v, k, s in zip(us, vs, is_mr, svals)]
    return reqs


def _oracle_answers(h, reqs):
    from repro.core import MSTOracle

    oracle = MSTOracle(h)
    out = []
    for r in reqs:
        mr = oracle.mr(r.u, r.v)
        out.append(mr if r.kind == "mr" else mr >= r.s)
    return out


def _per_call_loop(eng, reqs) -> float:
    t0 = time.perf_counter()
    for r in reqs:
        if r.kind == "mr":
            eng.mr(r.u, r.v)
        else:
            eng.s_reach(r.u, r.v, r.s)
    return time.perf_counter() - t0


def bench_backend(backend: str, h, reqs, want, per_call_sample: int) -> dict:
    """Per-call loop vs micro-batched service on one backend; service
    answers asserted equal to the mst-oracle reference."""
    from repro.api import serve

    svc = serve(h, backend, start=False)
    eng = svc.engine
    eng.mr(0, 1)                                     # warm the scalar path

    sample = reqs[:per_call_sample] if per_call_sample else reqs
    per_call_s = _per_call_loop(eng, sample) * (len(reqs) / len(sample))

    futs = svc.submit_many(reqs)                     # warm bucket shapes
    svc.drain()
    [f.result(timeout=0) for f in futs]
    t0 = time.perf_counter()
    futs = svc.submit_many(reqs)
    svc.drain()
    got = [f.result(timeout=0) for f in futs]
    service_s = time.perf_counter() - t0

    for r, g, w in zip(reqs, got, want):
        assert g == w, (backend, r, g, w)

    st = svc.stats()
    q = len(reqs)
    return {
        "backend": backend,
        "queries": q,
        "per_call_s": per_call_s,
        "per_call_sampled": len(sample),
        "service_s": service_s,
        "service_qps": q / service_s,
        "speedup": per_call_s / service_s,
        "batches": st.batches - st.batches // 2,     # timed pass only
        "bucket_histogram": {str(k): v
                             for k, v in sorted(st.bucket_histogram.items())},
        "answers_verified": q,
    }


def bench_scoped_refresh(n_components: int, chain_len: int,
                         n_queries: int) -> dict:
    """Service snapshot refresh after a scoped update: rows re-derived
    must be a fraction of n, answers still equal to the oracle."""
    from repro.api import serve
    from repro.core import apply_edge_edits, planted_chain_hypergraph

    h = planted_chain_hypergraph(n_components, chain_len, overlap=3,
                                 extra_size=2, seed=0)
    svc = serve(h, "hl-index", start=False)
    rng = np.random.default_rng(0)
    futs = svc.submit_many(_mixed_workload(h, rng, 64))
    svc.drain()                                      # resident snapshot up
    [f.result(timeout=0) for f in futs]

    anchor = h.edge(0)
    ins = [[int(anchor[0]), int(anchor[1]), h.n]]
    t0 = time.perf_counter()
    svc.update(inserts=ins)
    h2, _, _ = apply_edge_edits(h, ins, [])
    reqs = _mixed_workload(h2, rng, n_queries)
    futs = svc.submit_many(reqs)
    svc.drain()
    got = [f.result(timeout=0) for f in futs]
    update_and_refresh_s = time.perf_counter() - t0

    want = _oracle_answers(h2, reqs)
    for r, g, w in zip(reqs, got, want):
        assert g == w, (r, g, w)
    st = svc.stats()
    rows_per_refresh = st.rows_rederived - h.n       # first refresh was full
    assert 0 < rows_per_refresh < h2.n, (rows_per_refresh, h2.n)
    return {
        "components": n_components,
        "n": int(h2.n),
        "m": int(h2.m),
        "rows_rederived_after_scoped_update": int(rows_per_refresh),
        "rows_full": int(h2.n),
        "row_fraction": rows_per_refresh / h2.n,
        "update_and_refresh_s": update_and_refresh_s,
        "answers_verified": len(reqs),
    }


def run(n: int, m: int, n_queries: int, per_call_sample: int,
        components: int, chain_len: int, out_path: str,
        enforce_speedup: bool = True) -> dict:
    from repro.core import random_hypergraph

    # low vertex degree keeps the independent MSTOracle check over the
    # full workload tractable (its cost is deg_u * deg_v forest-BFS)
    h = random_hypergraph(n, m, seed=0)
    rng = np.random.default_rng(1)
    reqs = _mixed_workload(h, rng, n_queries)
    want = _oracle_answers(h, reqs)

    rows = [bench_backend("sharded", h, reqs, want, per_call_sample),
            bench_backend("hl-index", h, reqs, want, 0)]
    for row in rows:
        print(f"serving {row['backend']}: per-call {row['per_call_s']:.2f}s "
              f"vs service {row['service_s']:.2f}s "
              f"({row['service_qps']:.0f} q/s) -> {row['speedup']:.1f}x "
              f"[{row['answers_verified']} answers verified]")
    headline = rows[0]
    if enforce_speedup:
        assert headline["speedup"] >= 5.0, (
            f"micro-batched serving must be >= 5x a per-call loop on the "
            f"device-resident backend; measured {headline['speedup']:.2f}x")
    elif headline["speedup"] < 5.0:
        # --quick runs on noisy shared CI runners with a subsampled
        # per-call loop: record the miss loudly, don't fail the job
        print(f"WARNING: quick-mode speedup {headline['speedup']:.2f}x "
              f"< 5x (timing noise at tiny sizes; the full run enforces)")

    refresh = bench_scoped_refresh(components, chain_len,
                                   min(n_queries, 512))
    print(f"scoped refresh: {refresh['rows_rederived_after_scoped_update']}"
          f"/{refresh['rows_full']} rows re-derived "
          f"({refresh['row_fraction']:.1%}) after update on "
          f"{refresh['components']} components")

    doc = {
        "workload": {"n": n, "m": m, "queries": n_queries,
                     "mix": "50% MRRequest / 50% SReachRequest, s in 1..4"},
        "headline_speedup": headline["speedup"],
        "note": ("Steady-state (bucket shapes pre-warmed) service vs a "
                 "per-call eng.mr/eng.s_reach loop on the same engine; "
                 "every service answer asserted equal to the mst-oracle "
                 "reference.  The sharded row is the headline: per-call "
                 "queries on a device-resident snapshot pay one dispatch "
                 "each, micro-batching fuses them.  The hl-index row "
                 "documents the host merge-join floor a Python admission "
                 "queue cannot beat on CPU."),
        "backends": rows,
        "scoped_refresh": refresh,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(f"wrote {out_path}")
    return doc


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny sizes for the CI smoke job")
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--m", type=int, default=None)
    ap.add_argument("--queries", type=int, default=None)
    ap.add_argument("--per-call-sample", type=int, default=None,
                    help="subsample for the (slow) sharded per-call loop; "
                         "0 = run every query")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_serving.json"))
    args = ap.parse_args()
    enable_compile_cache()
    if args.quick:
        n = args.n or 500
        m = args.m or 160
        queries = args.queries or 2000
        sample = 200 if args.per_call_sample is None else args.per_call_sample
        components, chain_len = 4, 8
    else:
        n = args.n or 2000
        m = args.m or 512
        queries = args.queries or 10_000
        sample = 500 if args.per_call_sample is None else args.per_call_sample
        components, chain_len = 16, 20
    run(n, m, queries, sample, components, chain_len, args.out,
        enforce_speedup=not args.quick)


if __name__ == "__main__":
    main()
