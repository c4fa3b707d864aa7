#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the accelerator this host holds.

    python3 bench/run.py --workload walmart.point.open --seed 7 \\
        --seconds 10 --trace 0

The cell, its configuration, its traffic and its metrics are looked up
by name from ``BENCHMARK.json`` (see ``manifest.py``).  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, and last the ``checks``, each number compared with its
limit; those numbers are also the last lines of standard error.

It exits non-zero and prints no result when JAX finds no TPU, or fewer
chips than the cell asks for, or when the program under test
(``src/repro``) is not beside it.  JAX's persistent compilation cache is
kept in ``<checkout>/.jax_cache``.

``--control ladder`` is for proving the check: it swaps the program's
answers for the reference's control before comparing, and must read
``"correct": false``; the program's own check is then printed under
``program_checks``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("ladder",), default=None)
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        ap.error("--seconds must be > 0")
    sys.path.insert(0, str(BENCH))
    import manifest
    try:
        cell = manifest.cell(manifest.load(ROOT), args.workload, ROOT)
    except (KeyError, OSError) as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program under test ({ROOT / 'src' / 'repro'}) "
              f"is not in this checkout", file=sys.stderr)
        return 2
    # the cache lives in the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["TPU_LOG_DIR"] = "disabled"        # no runtime logs in /tmp
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing is measured elsewhere",
              file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips; JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    # cache every program, however quick to compile, so that a warm run
    # compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import harness
    result = harness.run(cell, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), t_start=T_START,
                         work_dir=ROOT / ".bench_out",
                         control=args.control)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
