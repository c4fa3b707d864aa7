#!/usr/bin/env python3
"""Find the knee of an open-loop cell: one process, one build, rising rates.

    python3 bench/sweep.py --workload walmart.point.open --seed 3 \\
        --seconds 5 --rates 4000,8000,12000,16000,20000

Builds the cell's configuration once, warms every bucket shape, then runs
one window per rate of the cell's traffic with only the rate changed.
For each rate it prints a JSON line: requests offered, the share answered
inside the window, the latency quartiles of the first and the last
quarter of the window's requests, and the generator's lag.  The knee is
the highest rate at which at least 99% of the offered requests are
answered inside the window and the backlog does not grow: the last
quarter's median latency is at most twice the first quarter's plus one
millisecond.  The cell's traffic file then fixes its rate at about four
fifths of the knee; the benchmark itself never searches for a rate.
Needs a TPU, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    import manifest
    cell = manifest.cell(manifest.load(ROOT), args.workload, ROOT)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["TPU_LOG_DIR"] = "disabled"        # no runtime logs in /tmp
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: JAX found no TPU", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    import numpy as np
    import harness
    import loadgen
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    make_request = harness.request_factory()
    svc, (n, _, _), _ = harness.start_serving(
        cell.config, args.seed, cell.config["service"]["max_batch"])
    svc.start()
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        spec = dict(cell.traffic)
        spec["arrivals"] = dict(spec["arrivals"], rate_rps=rate)
        traffic = loadgen.Traffic.from_dict(spec)
        st0 = svc.stats()
        log = loadgen.drive(svc, traffic, args.seed + k, n, args.seconds,
                            make_request=make_request)
        st1 = svc.stats()
        lat = log.latency_s() * 1e3
        q = max(log.count // 4, 1)
        first, last = lat[:q], lat[-q:]
        share = log.completed_in_window() / max(log.count, 1)
        grows = (np.nanmedian(last) > 2 * np.nanmedian(first) + 1.0)
        print(json.dumps({
            "rate_rps": rate, "offered": log.count,
            "answered_in_window_share": share,
            "backlog_grows": bool(grows),
            "sustained": bool(share >= 0.99 and not grows),
            "p50_ms": float(np.nanpercentile(lat, 50)),
            "p99_ms": float(np.nanpercentile(lat, 99)),
            "first_quarter_p50_ms": float(np.nanmedian(first)),
            "last_quarter_p50_ms": float(np.nanmedian(last)),
            "first_quarter_p99_ms": float(np.nanpercentile(first, 99)),
            "last_quarter_p99_ms": float(np.nanpercentile(last, 99)),
            "gen_lag_p99_ms": float(np.nanpercentile(log.lag_s() * 1e3,
                                                     99)),
            "batch_size_mean": (st1.answered - st0.answered)
            / max(st1.batches - st0.batches, 1)}), flush=True)
        time.sleep(0.5)
    svc.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
