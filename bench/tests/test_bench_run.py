"""The command refuses to measure where it cannot: no TPU, no program
beside it, an unknown cell.  It then prints no result."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


ARGS = ["--workload", "walmart.point.open", "--seed", "2147483653",
        "--seconds", "1", "--trace", "0"]


@pytest.mark.parametrize("trace", ["0", "1"])
def test_exits_nonzero_without_a_tpu(trace):
    p = run(ROOT, *ARGS[:-1], trace)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path, *ARGS)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_workload_is_refused():
    p = run(ROOT, "--workload", "no.such.cell", "--seed", "1",
            "--seconds", "1")
    assert p.returncode == 2 and p.stdout.strip() == ""
