"""Kernel-layer benchmarks.

Two jobs:

1. ``closure_bench`` — the original closure-layer rows for
   ``benchmarks.run`` (name,value,unit CSV).
2. ``main`` / ``BENCH_kernels.json`` — the device-native query path
   measured against its host baselines: the Pallas ``label_join``
   batched merge-join vs the per-call host merge-join loop and the
   fused-XLA ``DeviceSnapshot`` batch, and ``maxmin_matmul`` vs its
   jnp reference — each with an analytic roofline utilization from the
   kernel's tiled HBM traffic model (benchmarks.roofline constants).

Honesty note baked into the JSON: on a CPU host the Pallas kernels run
under **interpret mode**, so their wall-clock measures the Python
interpreter, not device bandwidth — the roofline fractions are only
meaningful on a real TPU/GPU, and the CPU numbers exist to pin the
bytes/FLOP model and the byte-identical answers, not to claim speed.
Every label-join answer is asserted equal to the fused-XLA batch and
spot-checked against the independent mst-oracle.

  PYTHONPATH=src python -m benchmarks.kernels_bench            # full
  PYTHONPATH=src python -m benchmarks.kernels_bench --quick    # CI
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (random_hypergraph, distinct_thresholds,
                        maxmin_closure, threshold_closure_mr, maxmin_matmul)
from repro.kernels import ref
from repro.compile_cache import enable_compile_cache

__all__ = ["closure_bench", "label_join_bench", "maxmin_bench", "main"]


def _t(fn, reps=3):
    fn()                                 # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps


def closure_bench(m: int = 512) -> List[Tuple[str, float, str]]:
    h = random_hypergraph(m // 2, m, min_size=2, max_size=6, seed=0)
    w = jnp.asarray(h.line_graph(np.int32).astype(np.float32))
    mm = w.shape[0]
    rounds = int(np.ceil(np.log2(mm)))
    thr = distinct_thresholds(np.asarray(w))
    s = thr.size
    rows = []

    f1 = jax.jit(lambda x: maxmin_closure(x, max_rounds=rounds))
    t1 = _t(lambda: f1(w))
    # maxmin closure: rounds × m³ compare+select ops (VPU work, 2 ops/elem)
    ops1 = rounds * 2 * mm ** 3
    rows.append((f"kernel.maxmin-closure.m{mm}", t1 * 1e6, "us-per-call"))
    rows.append((f"kernel.maxmin-closure.m{mm}.Gop", ops1 / 1e9, "Gops"))

    f2 = jax.jit(lambda x: threshold_closure_mr(x, thr, rounds=rounds))
    t2 = _t(lambda: f2(w))
    # threshold closure: rounds × S × 2m³ MAC (MXU work)
    ops2 = rounds * s * 2 * mm ** 3
    rows.append((f"kernel.threshold-closure.m{mm}.S{s}", t2 * 1e6,
                 "us-per-call"))
    rows.append((f"kernel.threshold-closure.m{mm}.Gop", ops2 / 1e9, "Gops"))

    # the single (max,min) matmul building block
    f3 = jax.jit(lambda x: maxmin_matmul(x, x))
    t3 = _t(lambda: f3(w))
    rows.append((f"kernel.maxmin-matmul.m{mm}", t3 * 1e6, "us-per-call"))
    return rows


# ---------------------------------------------------------------------------
# device-native query path: kernel vs host merge-join
# ---------------------------------------------------------------------------

def _roofline_fraction(bytes_moved: float, flops: float, secs: float):
    from benchmarks.roofline import HBM_BW, PEAK_FLOPS

    return {
        "hbm_bytes": bytes_moved,
        "flops": flops,
        "achieved_GBps": bytes_moved / secs / 1e9,
        "hbm_utilization": bytes_moved / secs / HBM_BW,
        "flops_utilization": flops / secs / PEAK_FLOPS,
    }


def label_join_bench(n: int, m: int, q: int, sample: int,
                     interpret: bool) -> dict:
    """Batched MR: per-call host merge-join vs fused-XLA snapshot batch
    vs the Pallas ``label_join`` kernel, answers pinned both ways."""
    from repro.api import build_engine
    from repro.core import MSTOracle
    from repro.core.query import KernelSnapshot

    h = random_hypergraph(n, m, seed=0)
    eng = build_engine(h, "hl-index")
    snap = eng.snapshot()
    kern = KernelSnapshot(snap, interpret=interpret)
    rng = np.random.default_rng(1)
    us = rng.integers(0, h.n, q).astype(np.int32)
    vs = rng.integers(0, h.n, q).astype(np.int32)

    sample = min(sample, q)
    t0 = time.perf_counter()
    host = [eng.mr(int(u), int(v))
            for u, v in zip(us[:sample], vs[:sample])]
    host_per_call = (time.perf_counter() - t0) / sample

    xla_t = _t(lambda: snap.mr(us, vs))
    kern_t = _t(lambda: kern.mr(us, vs))

    xla_out = np.asarray(snap.mr(us, vs)).astype(np.int64)
    kern_out = np.asarray(kern.mr(us, vs)).astype(np.int64)
    np.testing.assert_array_equal(kern_out, xla_out)   # byte-identical
    oracle = MSTOracle(h)
    for u, v, got in zip(us[:sample], vs[:sample], kern_out[:sample]):
        assert got == oracle.mr(int(u), int(v))
    assert host == list(kern_out[:sample])

    # tiled traffic model for grid (Q/bq, L/bl, L/bl), k innermost:
    # u rows (ranks+svals, int32 pairs) stream once per (i, j); v rows
    # re-stream for every (j, k); the [bq] output tile lives in VMEM
    # across the whole (j, k) sweep and is written once.
    L = int(snap.ranks.shape[1])
    bl = min(256, max(L, 1))
    qpad = max(q, 1)
    sweeps = max(1, -(-L // bl))
    bytes_moved = (qpad * L * 8) + (qpad * L * 8 * sweeps) + qpad * 4
    flops = 3.0 * qpad * L * L            # eq, min, max per rank pair

    return {
        "graph": {"n": h.n, "m": h.m, "label_width_L": L},
        "batch_q": q,
        "host_merge_join_per_call_us": host_per_call * 1e6,
        "host_merge_join_batch_us": host_per_call * q * 1e6,
        "xla_snapshot_batch_us": xla_t * 1e6,
        "pallas_label_join_batch_us": kern_t * 1e6,
        "interpret_mode": interpret,
        "answers_verified": int(q),
        "roofline": _roofline_fraction(bytes_moved, flops, kern_t),
    }


def maxmin_bench(mm: int, interpret: bool) -> dict:
    """One (max,min) contraction step of the sharded closure: Pallas
    kernel vs the jnp reference, both over the same [m, m] operand."""
    from repro.kernels.maxmin_matmul import maxmin_matmul_pallas

    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.integers(0, 100, (mm, mm)).astype(np.int32))

    ref_fn = jax.jit(ref.maxmin_matmul_ref)
    ref_t = _t(lambda: ref_fn(a, a))
    kern_t = _t(lambda: maxmin_matmul_pallas(a, a, interpret=interpret))

    np.testing.assert_array_equal(
        np.asarray(maxmin_matmul_pallas(a, a, interpret=interpret)),
        np.asarray(ref_fn(a, a)))

    # tiled traffic, grid (M/bm, N/bn, K/bk) k innermost: a block (i, k)
    # streams once per j sweep, b block (k, j) once per (i, j, k); the
    # [bm, bn] accumulator is VMEM-resident across the k sweep.
    bm = bn = 128
    bytes_moved = (mm * mm * 4 * max(1, -(-mm // bn)) +
                   mm * mm * 4 * max(1, -(-mm // bm)) + mm * mm * 4)
    flops = 2.0 * mm ** 3                  # min + max per element

    return {
        "m": mm,
        "xla_reference_us": ref_t * 1e6,
        "pallas_kernel_us": kern_t * 1e6,
        "interpret_mode": interpret,
        "roofline": _roofline_fraction(bytes_moved, flops, kern_t),
    }


def run(n: int, m: int, q: int, sample: int, mm: int,
        out_path: str) -> dict:
    from repro.kernels.ops import use_interpret

    interpret = use_interpret()
    lj = label_join_bench(n, m, q, sample, interpret)
    mx = maxmin_bench(mm, interpret)
    print(f"label_join: host {lj['host_merge_join_batch_us']:.0f}us "
          f"(per-call x{q}) | xla batch {lj['xla_snapshot_batch_us']:.0f}us "
          f"| pallas {lj['pallas_label_join_batch_us']:.0f}us "
          f"(interpret={interpret})")
    print(f"maxmin_matmul m={mm}: xla ref {mx['xla_reference_us']:.0f}us "
          f"| pallas {mx['pallas_kernel_us']:.0f}us")
    doc = {
        "note": ("Pallas device-native query path vs host baselines.  "
                 "interpret_mode=true means the kernels ran under the "
                 "Pallas interpreter (no TPU/GPU on this host): their "
                 "wall-clock measures the interpreter, the roofline "
                 "utilizations are meaningful only on device, and the "
                 "numbers pin the traffic model and byte-identical "
                 "answers, not speed.  Every label_join answer is "
                 "asserted equal to the fused-XLA snapshot batch and "
                 "spot-checked against the mst-oracle."),
        "label_join": lj,
        "maxmin_matmul": mx,
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
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_kernels.json"))
    args = ap.parse_args()
    enable_compile_cache()
    if args.quick:
        run(n=200, m=160, q=512, sample=128, mm=128, out_path=args.out)
    else:
        run(n=1000, m=800, q=2048, sample=256, mm=512, out_path=args.out)


if __name__ == "__main__":
    main()
