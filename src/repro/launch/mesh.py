"""Production mesh construction.

Kept as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
``--xla_force_host_platform_device_count=512`` before first jax init and
only then calls these.
"""
from __future__ import annotations

from jax.sharding import Mesh

from repro.compat import make_mesh as _make_mesh

__all__ = ["make_production_mesh", "make_test_mesh"]


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: 16×16 = 256 chips, axes (data, model).
    Multi-pod: 2×16×16 = 512 chips, axes (pod, data, model) — the ``pod``
    axis carries only the threshold batch across the inter-pod DCI."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")) -> Mesh:
    """Small mesh for CPU tests (requires the host-device-count flag)."""
    return _make_mesh(shape, axes)
