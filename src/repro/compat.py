"""The JAX 0.9 mesh and ``shard_map`` spellings used across the package.

* ``shard_map``: ``jax.shard_map`` (``check_vma`` toggles the
  varying-manual-axes check).
* ``make_mesh``: every axis ``AxisType.Auto``, so GSPMD keeps
  propagating shardings through the jitted programs around the
  ``shard_map`` bodies.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

shard_map = jax.shard_map


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
