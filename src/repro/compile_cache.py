"""Where JAX keeps its persistent compilation cache for this checkout.

The cache key includes the directory, so a cache only pays off at a
fixed path.  ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX
itself and left alone; otherwise the entry points (``chip_smoke.py``,
the benchmarks) place the cache at ``<repo>/.jax_cache``.  Nothing calls
this at import time, so the test suite stays uncached.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
