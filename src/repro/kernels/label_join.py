"""Pallas TPU kernel: batched HL-index label join (Algorithm 5).

out[q] = max over common hubs of min(s_u[q], s_v[q]) — the serving-path
inner loop.  Each query row holds two padded, rank-sorted label lists; the
kernel evaluates the all-pairs hub-equality join on the VPU, which beats
the sequential two-pointer merge on a vector unit for the label lengths
the paper reports (avg |L| well under 128).

Layout: the wrapper hands the kernel transposed label tiles, [bl, bq]
with label slots on sublanes and queries on lanes.  One v-side label
slot is then a single sublane row ``[1, bq]`` that broadcasts along
sublanes over the u-side tile, so each slot costs one 2-D
compare-select-max on ``[bl, bq]`` and nothing wider.  The [bq, bl, bl]
broadcast cube of the textbook formulation never exists (Mosaic cannot
lay it out, and at bq=128, bl=256 it would be 32 MiB of int32).

Tiling: grid (Q/bq, L/bl, L/bl).  The output block ``o[1, bq]`` is
indexed by the query dimension only, so it stays VMEM-resident across
the whole (j, k) label-tile sweep (initialized on the first tile,
max-accumulated after) — the per-step working set is bounded by four
[bl, bq] tiles no matter how wide the label rows are.  That is what
keeps closure-derived snapshots (where L = m) and heavy-tail label rows
inside VMEM.

Sentinel contract (shared with ``DeviceSnapshot`` / ``pad_label_rows``):

* rank padding is ``INT32_MAX`` (2^31 - 1) on both operands;
* query rows added to reach a ``bq`` multiple carry ``INT32_MAX - 1`` on
  the u side, so an all-padding row never self-matches;
* therefore **real ranks must be <= MAX_RANK = 2^31 - 3**: a real rank
  equal to either sentinel would alias padding.  Rank keys are hyperedge
  importance ranks (or raw hyperedge ids for closure snapshots), so the
  bound is m <= 2^31 - 2 hyperedges — ``validate_ranks`` asserts it once
  per snapshot (``KernelSnapshot``), not per query batch.  Pad-pad
  matches themselves are inert either way: padding svals are 0, the
  identity of the join max.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["label_join_pallas", "validate_ranks", "MAX_RANK"]

_PAD = np.iinfo(np.int32).max          # rank-slot padding (both operands)
_PAD_ROW = _PAD - 1                    # u-side padded *query rows*
MAX_RANK = _PAD - 2                    # largest legal real rank (2^31 - 3)


def validate_ranks(ranks) -> None:
    """Raise if any real rank aliases a padding sentinel.

    One host-visible reduction; callers run it once per snapshot (not
    per batch).  The padded label form uses ``INT32_MAX`` for empty
    slots and ``INT32_MAX - 1`` for whole padded query rows, so real
    ranks above ``MAX_RANK`` would silently join against padding.
    """
    ranks = jnp.asarray(ranks)
    if ranks.size == 0:
        return
    real_max = int(jnp.where(ranks == _PAD, -1, ranks).max())
    if real_max > MAX_RANK:
        raise ValueError(
            f"label rank {real_max} aliases the padding sentinels; the "
            f"kernel join supports real ranks <= {MAX_RANK} (2^31 - 3), "
            f"i.e. at most 2^31 - 2 hyperedges")


def _kernel(ru_ref, su_ref, rv_ref, sv_ref, o_ref):
    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    ru = ru_ref[...]                   # [bl, bq] u-side label tile j
    su = su_ref[...]

    def one_slot(c, acc):
        rv = rv_ref[pl.ds(c, 1), :]    # [1, bq] v-side label slot c
        sv = sv_ref[pl.ds(c, 1), :]
        return jnp.maximum(acc, jnp.where(ru == rv, jnp.minimum(su, sv), 0))

    acc = jax.lax.fori_loop(0, rv_ref.shape[0], one_slot,
                            jnp.zeros_like(su))
    o_ref[...] = jnp.maximum(o_ref[...], acc.max(axis=0, keepdims=True))


@functools.partial(jax.jit, static_argnames=("bq", "bl", "interpret"))
def label_join_pallas(ru: jax.Array, su: jax.Array, rv: jax.Array,
                      sv: jax.Array, *, bq: int = 128, bl: int = 256,
                      interpret: bool = False) -> jax.Array:
    """ru/rv [Q, L] int32 ascending ranks (INT32_MAX pad — padding never
    matches since real ranks <= MAX_RANK), su/sv [Q, L] int32 (0 pad).
    Returns [Q] int32.  Q and L need not be block multiples; Q = 0 and
    L = 0 are legal (nothing joins: all zeros)."""
    q, lmax = ru.shape
    if q == 0 or lmax == 0:
        return jnp.zeros((q,), su.dtype)
    bl = min(bl, lmax)                 # one tile when the rows are narrow
    qpad, lpad = (-q) % bq, (-lmax) % bl
    if qpad or lpad:
        ru = jnp.pad(ru, ((0, qpad), (0, lpad)), constant_values=_PAD)
        rv = jnp.pad(rv, ((0, qpad), (0, lpad)), constant_values=_PAD)
        su = jnp.pad(su, ((0, qpad), (0, lpad)))
        sv = jnp.pad(sv, ((0, qpad), (0, lpad)))
    if qpad:
        # padded query rows: ranks all-INT32_MAX on both sides would
        # "match"; force the u-side pad rows to the row sentinel (their
        # answers are sliced off below either way)
        ru = ru.at[q:, :].set(_PAD_ROW)
    qg = ru.shape[0] // bq
    lg = ru.shape[1] // bl

    # queries on lanes, label slots on sublanes (see module docstring)
    ru, su, rv, sv = ru.T, su.T, rv.T, sv.T
    out = pl.pallas_call(
        _kernel,
        grid=(qg, lg, lg),
        in_specs=[
            pl.BlockSpec((bl, bq), lambda i, j, k: (j, i)),   # u ranks
            pl.BlockSpec((bl, bq), lambda i, j, k: (j, i)),   # u svals
            pl.BlockSpec((bl, bq), lambda i, j, k: (k, i)),   # v ranks
            pl.BlockSpec((bl, bq), lambda i, j, k: (k, i)),   # v svals
        ],
        out_specs=pl.BlockSpec((1, bq), lambda i, j, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, qg * bq), su.dtype),
        interpret=interpret,
    )(ru, su, rv, sv)
    return out[0, :q]
