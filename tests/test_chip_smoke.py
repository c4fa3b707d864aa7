"""``chip_smoke.py`` and the compile-cache helper, on the CPU.

The smoke run itself needs a TPU and must refuse everything else; its
phases run here at small sizes (kernels interpreted), which is the
rehearsal that catches wrong paths and control flow before chip time.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.compile_cache import CACHE_DIR, enable_compile_cache
from util_subproc import run_with_devices

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"
SMALL = dict(n=3000, m=2400, min_size=2, max_size=8)


def _smoke_module():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def cache_dir_setting():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path,
                                               cache_dir_setting):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_into_the_checkout(monkeypatch,
                                                  cache_dir_setting):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


_CACHED_COMPILE = """
import pathlib, sys
import jax, jax.numpy as jnp
import repro.compile_cache as cc
cc.CACHE_DIR = pathlib.Path(sys.argv[1])
hits = []
jax.monitoring.register_event_listener(
    lambda e, **_: e == "/jax/compilation_cache/cache_hits" and hits.append(e))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
assert cc.enable_compile_cache() == sys.argv[1]
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
print("HITS", len(hits))
"""


def test_compile_cache_is_read_back_by_the_next_process(tmp_path):
    # the helper runs after ``import jax``; the cache it places must
    # still be written by the first process and read by the second
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    hits = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", _CACHED_COMPILE,
                              str(tmp_path)], env=env, capture_output=True,
                             text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        hits.append(int(out.stdout.split("HITS")[-1]))
    assert hits[0] == 0 and hits[1] > 0
    assert any(tmp_path.iterdir())


def _run(script: Path, cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_smoke_refuses_a_host_without_tpu():
    out = _run(SMOKE, ROOT)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_smoke_refuses_to_run_outside_the_checkout(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, alone)
    out = _run(alone, tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "checkout" in out.stderr


def test_smoke_phases_at_small_size(capsys):
    cs = _smoke_module()
    clock = cs.Clock()
    eng, reqs, answers = cs.phase_main_path(clock, 0, n_requests=600,
                                            graph=SMALL)
    cs.phase_kernel_path(clock, eng, reqs, answers)
    # seed 2 -> a closure graph with a small component to edit
    cs.phase_closure_regime(clock, 2, m=512, n_requests=200)
    out = capsys.readouterr().out
    for phase in ("phase a:", "phase b:", "phase c:"):
        assert phase in out and out.count(" ok") >= 3


def test_smoke_mesh_phase_on_four_host_devices():
    out = run_with_devices(
        "import importlib.util, jax\n"
        f"spec = importlib.util.spec_from_file_location('s', {str(SMOKE)!r})\n"
        "cs = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(cs)\n"
        "assert len(jax.devices()) == 4\n"
        "clock = cs.Clock()\n"
        "cs.phase_mesh(clock, 0, m=256, n_queries=128)\n"
        f"cs.phase_mesh_construction(clock, 0, graph={SMALL!r}, workers=2)\n"
    )
    assert out.count(" ok") == 5 and "pool_workers=2" in out
