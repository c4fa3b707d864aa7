"""Benchmark entry point: one function per paper table.  Prints
``name,value,unit`` CSV rows (per-query us, total-us, bytes, counts).

  PYTHONPATH=src python -m benchmarks.run            # full suite
  PYTHONPATH=src python -m benchmarks.run --quick    # CI-speed subset
"""
from __future__ import annotations

import argparse
import sys

from repro.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    from . import paper_tables as pt
    from . import kernels_bench as kb

    rows = []
    # Exp-1: query time (paper Fig. 2)
    for ds in (["NC-s", "BK-s"] if args.quick else
               ["NC-s", "BK-s", "PS-s", "EE-s"]):
        rows += pt.exp1_query_time(ds, n_q=300 if args.quick else 1000,
                                   include_online=not args.quick or ds == "NC-s")
    # Exp-2: indexing time (Table IV, time)
    for ds in (["NC-s"] if args.quick else ["NC-s", "BK-s", "PS-s"]):
        rows += pt.exp2_indexing_time(ds, include_basic=(ds == "NC-s"))
    # Exp-3: space (Table IV, space)
    for ds in (["BK-s"] if args.quick else ["NC-s", "BK-s", "EE-s"]):
        rows += pt.exp3_space(ds)
    # Exp-4: scalability (Fig. 3)
    if not args.quick:
        rows += pt.exp4_scalability("WA-s")
    # Exp-5: case study (Fig. 4)
    rows += pt.exp5_case_study()
    # unified engine API: every registered backend built, benchmarked and
    # cross-validated through the repro.api facade
    rows += pt.engine_suite("ENG-s", n_q=64 if args.quick else 128)
    # sharded backend vs single-device closure, both collective schedules
    # (multi-device numbers come from benchmarks/bench_sharded.py)
    rows += pt.sharded_suite("ENG-s", n_q=64 if args.quick else 128)
    # kernel/closure layer
    rows += kb.closure_bench(m=256 if args.quick else 512)

    print("name,value,unit")
    for name, val, unit in rows:
        print(f"{name},{float(val):.3f},{unit}")


if __name__ == "__main__":
    main()
