"""Quickstart: one API surface for every reachability backend.

  PYTHONPATH=src python examples/quickstart.py
"""
import time

import numpy as np

from repro.api import (build_engine, available_backends, plan_backend,
                       paper_figure1, random_hypergraph, compact)


def main():
    # --- the paper's running example (Figure 1) ---------------------------
    h = paper_figure1()
    eng = build_engine(h, backend="hl-index")
    print("Figure-1 hypergraph:", h.stats())
    print("MR(v5, v9)  =", eng.mr(4, 8), " (paper Example 1: 2)")
    print("MR(v1, v12) =", eng.mr(0, 11), "(paper Example 4: 2)")
    print("v1 ~2~> v10 ?", eng.s_reach(0, 9, 2), "(paper Example 3: True)")

    # --- a bigger graph: build once, serve through the same surface -------
    h = random_hypergraph(3000, 4500, min_size=2, max_size=8, seed=0)
    h, _ = compact(h)
    t0 = time.perf_counter()
    eng = build_engine(h, backend="hl-index")
    t_build = time.perf_counter() - t0
    print(f"\nn={h.n} m={h.m}: hl-index build {t_build:.2f}s "
          f"({eng.nbytes()} bytes); planner would pick "
          f"{plan_backend(h, batch_hint=10_000)!r} for this shape")

    rng = np.random.default_rng(0)
    us, vs = rng.integers(0, h.n, 10000), rng.integers(0, h.n, 10000)

    # online (index-free) vs hl-index on a few queries — same protocol
    online = build_engine(h, backend="online")
    t0 = time.perf_counter()
    online_ans = [online.mr(int(u), int(v)) for u, v in zip(us[:20], vs[:20])]
    t_online = (time.perf_counter() - t0) / 20
    t0 = time.perf_counter()
    idx_ans = [eng.mr(int(u), int(v)) for u, v in zip(us[:20], vs[:20])]
    t_idx = (time.perf_counter() - t0) / 20
    assert online_ans == idx_ans
    print(f"per-query: online {t_online*1e3:.2f} ms  vs  "
          f"hl-index {t_idx*1e6:.1f} us  ({t_online/t_idx:.0f}x)")

    # the device snapshot: 10k queries in one fused XLA program
    snap = eng.snapshot()
    ans = np.asarray(snap.mr(us, vs))     # includes compile
    t0 = time.perf_counter()
    ans = np.asarray(snap.mr(us, vs))
    t_batch = time.perf_counter() - t0
    print(f"device snapshot: 10,000 queries in {t_batch*1e3:.1f} ms "
          f"({t_batch/len(us)*1e9:.0f} ns/query); "
          f"max MR in batch = {ans.max()}")
    print("registered backends:", ", ".join(available_backends()))

    # --- live updates: scoped maintenance through the same engine ---------
    # construction reruns only on the affected line-graph component, so
    # on a multi-component graph updates cost ~1/C of a rebuild
    from repro.api import planted_chain_hypergraph
    hc = planted_chain_hypergraph(16, 40, overlap=3, extra_size=2, seed=0)
    t0 = time.perf_counter()
    ec = build_engine(hc, backend="hl-index")
    t_build_c = time.perf_counter() - t0
    snap_c = ec.snapshot()
    anchor = [int(v) for v in hc.edge(0)[:2]]
    t0 = time.perf_counter()
    ec.update(inserts=[anchor + [hc.n]], deletes=[hc.m - 1])
    t_upd = time.perf_counter() - t0
    print(f"\nupdate on {hc.m}-edge, 16-component graph "
          f"(1 insert + 1 delete): {t_upd*1e3:.1f} ms scoped vs "
          f"{t_build_c*1e3:.0f} ms full build "
          f"({t_build_c/t_upd:.0f}x); engine version -> {ec.version} "
          f"(old snapshots are stale: {snap_c.version} != {ec.version})")
    assert ec.snapshot() is not snap_c    # re-derived, serves new answers


if __name__ == "__main__":
    main()
