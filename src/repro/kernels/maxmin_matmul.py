"""Pallas TPU kernel: (max, min)-semiring matmul.

C[i,j] = max_k min(A[i,k], B[k,j]) — the inner step of the bottleneck-path
closure (the paper's max-reachability between hyperedges).

TPU mapping: the MXU cannot evaluate a (max, min) contraction (it is a
fixed multiply-accumulate array), so this kernel is VPU work.  The design
goal is therefore *bandwidth*: stream 128-aligned A/B tiles HBM→VMEM once
per (i, j, k) grid step and sweep the tile's k columns as rank-1 updates
on the [bm, bn] accumulator: column ``A[:, k]`` broadcast along lanes,
row ``B[k, :]`` along sublanes, one 2-D min + max each.  Every k slice is
static, so Mosaic sees only aligned loads and 2-D vector ops (a
``dynamic_slice`` along the lane dim does not lower).  The rank-1
terms of ``k_chunk`` columns are max-combined into one partial before it
meets the accumulator, which shortens the loop-carried max chain.

Grid: (M/bm, N/bn, K/bk) with k innermost so the output block stays
resident in VMEM across the k sweep (revisiting accumulation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["maxmin_matmul_pallas"]


def _kernel(a_ref, b_ref, o_ref, *, k_chunk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bk = a_ref.shape[1]
    acc = o_ref[...]                    # [bm, bn]
    for k0 in range(0, bk, k_chunk):    # static: the tail chunk is short
        part = None
        for k in range(k0, min(k0 + k_chunk, bk)):
            c = jnp.minimum(a_ref[:, k:k + 1], b_ref[k:k + 1, :])
            part = c if part is None else jnp.maximum(part, c)
        acc = jnp.maximum(acc, part)
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "k_chunk",
                                             "interpret"))
def maxmin_matmul_pallas(a: jax.Array, b: jax.Array, *, bm: int = 128,
                         bn: int = 128, bk: int = 128, k_chunk: int = 8,
                         interpret: bool = False) -> jax.Array:
    """(max, min) matmul with explicit VMEM tiling.  Non-negative inputs;
    shapes are padded to block multiples with the semiring zero.  Empty
    operands (m, n or k of 0) return the semiring-zero result directly."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    if m == 0 or n == 0 or k == 0:
        return jnp.zeros((m, n), a.dtype)
    mp, np_, kp = (-m) % bm, (-n) % bn, (-k) % bk
    if mp or kp:
        a = jnp.pad(a, ((0, mp), (0, kp)))
    if np_ or kp:
        b = jnp.pad(b, ((0, kp), (0, np_)))
    mg, ng, kg = a.shape[0] // bm, b.shape[1] // bn, a.shape[1] // bk

    out = pl.pallas_call(
        functools.partial(_kernel, k_chunk=min(k_chunk, bk)),
        grid=(mg, ng, kg),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((a.shape[0], b.shape[1]), a.dtype),
        interpret=interpret,
    )(a, b)
    return out[:m, :n]
