"""(max, min)-semiring formulation of max-reachability — the TPU-native
re-expression of the paper's problem, and the oracle for all tests.

Key identity (``docs/ARCHITECTURE.md``, "The closure regime"): with
``W[i,j] = OD(e_i, e_j)`` (diagonal ``|e_i|``), the hyperedge-level
max-reachability matrix is the bottleneck-path closure ``W*`` under the
(max, min) semiring, and

    MR(u, v) = max_{e_u ∋ u, e_v ∋ v} W*[e_u, e_v].

The ``closure`` backend serves the per-vertex rows of that closure,
``R[v, e] = max_{e_v ∋ v} W*[e_v, e]`` (``vertex_rows``).  They are
built on the device without W*: start from ``R[v, e] = |e|`` for each
``e ∋ v`` and repeat ``R ← max(R, R ⊗ W)`` until a round changes
nothing.  One round is an n·m² (max, min) contraction, and the rounds
are the hops of the longest optimal walk plus one that changes nothing,
so ``n ≪ m`` graphs never pay for the m³ squaring of W.

Whole-matrix closures, kept as references for the tests and for the
``sharded`` backend:

* ``maxmin_closure`` — repeated squaring with the (max, min) matmul.
  Exact, O(log diam) rounds of an m³ VPU op (no MXU semiring support).
* ``threshold_closure_mr`` — re-expresses the same closure as a batch of
  *boolean* transitive closures over overlap thresholds, each computed
  with real bf16/f32 matmuls → MXU work.  ``MR[i,j] = max{s : reach_s}``.
  Exact when ``thresholds`` = all distinct OD values (the default).

Both consume the dense line graph; the framework's scalability story for
huge W* is the 2-D block-sharded version in ``distributed.py``.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .hypergraph import Hypergraph
from ..stages import Stages

__all__ = [
    "maxmin_matmul", "maxmin_closure", "boolean_closure",
    "threshold_closure_mr", "mr_matrix", "mr_oracle_dense",
    "vertex_mr_from_edge_mr", "distinct_thresholds", "vertex_rows",
    "edge_mr_rows",
]


def maxmin_matmul(a: jax.Array, b: jax.Array, *, block: int = 512) -> jax.Array:
    """C[i,j] = max_k min(A[i,k], B[k,j]) for non-negative inputs.

    Pure-jnp reference; the Pallas kernel (kernels/maxmin_matmul.py)
    implements the same contraction with explicit VMEM tiling.  Blocked
    over k to bound the [i,k,j] broadcast.  Zero is the (max, min)
    annihilator/identity pair on the non-negative domain, so zero padding
    of the contraction dim is exact.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    if k <= block:
        return jnp.minimum(a[:, :, None], b[None, :, :]).max(axis=1)
    pad = (-k) % block
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad)))
        b = jnp.pad(b, ((0, pad), (0, 0)))

    def body(carry, kk):
        a_blk = jax.lax.dynamic_slice(a, (0, kk), (m, block))
        b_blk = jax.lax.dynamic_slice(b, (kk, 0), (block, n))
        c = jnp.minimum(a_blk[:, :, None], b_blk[None, :, :]).max(axis=1)
        return jnp.maximum(carry, c), None

    init = jnp.zeros((m, n), a.dtype)
    nblk = (k + pad) // block
    out, _ = jax.lax.scan(body, init, jnp.arange(nblk) * block)
    return out


def maxmin_closure(w: jax.Array, *, block: int = 512,
                   max_rounds: Optional[int] = None) -> jax.Array:
    """Bottleneck-path closure by repeated squaring:
    R ← max(R, R∘R) until fixpoint (≤ ⌈log2 m⌉ rounds)."""
    m = w.shape[0]
    rounds = max_rounds if max_rounds is not None else max(1, int(np.ceil(np.log2(max(m, 2)))))

    def step(r, _):
        r2 = jnp.maximum(r, maxmin_matmul(r, r, block=block))
        return r2, None

    out, _ = jax.lax.scan(step, w, None, length=rounds)
    return out


def boolean_closure(adj: jax.Array, *, rounds: Optional[int] = None) -> jax.Array:
    """Transitive closure of a boolean adjacency (float 0/1) via repeated
    squaring with *real* matmuls — the MXU-friendly primitive.
    adj must include self-loops for closure semantics."""
    m = adj.shape[-1]
    n_rounds = rounds if rounds is not None else max(1, int(np.ceil(np.log2(max(m, 2)))))

    def step(r, _):
        r2 = (r @ r > 0).astype(adj.dtype)
        return r2, None

    out, _ = jax.lax.scan(step, adj, None, length=n_rounds)
    return out


def closure_rounds_to_fixpoint(w: jax.Array, *, block: int = 512,
                               max_rounds: int = 64) -> int:
    """Squaring rounds until the bottleneck closure stops changing —
    ⌈log2(effective s-walk diameter)⌉, typically 3-6 on real hypergraphs
    vs the worst-case ⌈log2 m⌉ ladder.  The measured number drives the
    early-exit optimization in §Perf C (a host-side convergence check per
    round costs one [m,m] equality-reduce)."""
    r = w
    for i in range(1, max_rounds + 1):
        r2 = jnp.maximum(r, maxmin_matmul(r, r, block=block))
        if bool(jnp.array_equal(r2, r)):
            return i
        r = r2
    return max_rounds


def distinct_thresholds(w: np.ndarray) -> np.ndarray:
    """All distinct positive entries of the line graph (off-diagonal OD
    values and diagonal |e| values), ascending."""
    vals = np.unique(w)
    return vals[vals > 0]


@functools.partial(jax.jit, static_argnames=("rounds",))
def _threshold_batch_closure(w: jax.Array, thresholds: jax.Array,
                             rounds: int) -> jax.Array:
    """reach[s_idx, i, j] ∈ {0,1}: closure of (W ≥ t) per threshold.
    vmap over the threshold batch → one batched matmul per squaring round
    (a [S, m, m] × [S, m, m] batched contraction: pure MXU work)."""
    adj = (w[None, :, :] >= thresholds[:, None, None]).astype(jnp.float32)
    eye = jnp.eye(w.shape[0], dtype=jnp.float32)[None]
    adj = jnp.maximum(adj, eye)

    def step(r, _):
        return (jax.lax.batch_matmul(r, r) > 0).astype(jnp.float32), None

    out, _ = jax.lax.scan(step, adj, None, length=rounds)
    return out


def threshold_closure_mr(w: jax.Array, thresholds: Optional[np.ndarray] = None,
                         *, rounds: Optional[int] = None) -> jax.Array:
    """MR matrix via threshold-batched boolean closure.

    Exact iff ``thresholds`` covers every distinct positive value of W
    (default).  A coarser ladder gives a lower bound — the bucketized
    (approximate) mode used when δ is huge; see DESIGN.md §2.
    """
    if thresholds is None:
        thresholds = distinct_thresholds(np.asarray(w))
    thresholds = np.asarray(thresholds)
    if thresholds.size == 0:
        return jnp.zeros_like(w)
    m = w.shape[0]
    n_rounds = rounds if rounds is not None else max(1, int(np.ceil(np.log2(max(m, 2)))))
    reach = _threshold_batch_closure(jnp.asarray(w), jnp.asarray(thresholds),
                                     n_rounds)                     # [S, m, m]
    # MR[i,j] = largest threshold whose closure connects i and j.
    t = jnp.asarray(thresholds).astype(w.dtype)
    mr = (reach * t[:, None, None]).max(axis=0)
    # reach includes the trivial i==i at every threshold via self-loops; fix
    # the diagonal to the true single-walk value |e_i| = W[i,i].
    mr = mr.at[jnp.arange(m), jnp.arange(m)].set(jnp.diagonal(w))
    return mr


def mr_matrix(h: Hypergraph) -> np.ndarray:
    """Hyperedge-level MR matrix W* [m, m] for a whole hypergraph: the
    dense host overlap matrix closed by squaring (a test reference; the
    ``closure`` backend builds ``vertex_rows`` instead)."""
    if h.m == 0:                # no hyperedges: nothing is reachable
        return np.zeros((0, 0), np.int32)
    w_star = maxmin_closure(jnp.asarray(h.line_graph(np.int32)))
    return np.asarray(w_star).astype(np.int32, copy=False)


def edge_mr_rows(h: Hypergraph, w_star: np.ndarray) -> np.ndarray:
    """The vertex rows ``R[v, e] = max_{e_v ∋ v} W*[e_v, e]`` from a
    whole closure: a segment-max of W*'s rows over each vertex's
    incidence list (degree-0 vertices keep zero rows).  Reads W* saved
    by older stores; ``vertex_rows`` builds the same rows without W*."""
    w_star = np.asarray(w_star, np.int32)
    rows = np.zeros((h.n, h.m), np.int32)
    nz = np.nonzero(np.diff(h.v_ptr) > 0)[0]
    if nz.size:
        rows[nz] = np.maximum.reduceat(w_star[h.v_idx], h.v_ptr[nz], axis=0)
    return rows


@functools.partial(jax.jit, static_argnames=("n",))
def _line_graph(members: jax.Array, sizes: jax.Array, *, n: int):
    """The seed rows and the overlap matrix, on the device.  ``members``
    [k, s_max] lists each hyperedge's vertices (-1 pads a short one, and
    the rows past m, which pad the contraction); the transposed
    incidence ``inc[e, v]`` is a compare against them, ``W = inc·incᵀ``
    (int8 operands, int32 sums on the MXU; diagonal |e|) is [k, m], and
    the seed is ``R[v, e] = |e|`` for each e ∋ v."""
    m = sizes.shape[0]
    inc = (members[:, :, None] == jnp.arange(n)[None, None, :]).any(axis=1)
    inc = inc.astype(jnp.int8)                               # [k, n]
    w = jax.lax.dot_general(inc, inc[:m], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.int32)
    r0 = jnp.where(inc[:m].T > 0, sizes[None, :], 0)
    return r0, w


@functools.partial(jax.jit, static_argnames=("block",))
def _rows_fixpoint(r0: jax.Array, w: jax.Array, *, block: int):
    """``R ← max(R, R ⊗ W)`` until a round changes nothing, at most m
    rounds, in one device loop.  Returns the rows and the rounds run
    (the last one changed nothing)."""
    n, m = r0.shape
    pad = w.shape[0] - m

    def cond(carry):
        _, changed, rounds = carry
        return changed & (rounds < m)

    def body(carry):
        r, _, rounds = carry
        prod = maxmin_matmul(jnp.pad(r, ((0, 0), (0, pad))), w, block=block)
        r2 = jnp.maximum(r, prod)
        return r2, jnp.any(r2 != r), rounds + 1

    r, _, rounds = jax.lax.while_loop(
        cond, body, (r0, jnp.bool_(True), jnp.int32(0)))
    return r, rounds


def _members(h: Hypergraph, block: int) -> np.ndarray:
    """[k, s_max] int32: row e lists hyperedge e's vertices, -1 after
    them and in the rows from m up to k, the next multiple of
    ``block``."""
    sizes = h.edge_sizes
    k = -(-h.m // block) * block
    out = np.full((k, max(int(sizes.max()), 1)), -1, np.int32)
    col = np.arange(h.e_idx.size) - np.repeat(h.e_ptr[:-1], sizes)
    out[np.repeat(np.arange(h.m), sizes), col] = h.e_idx
    return out


def vertex_rows(h: Hypergraph, *, stages: Optional[Stages] = None,
                block: int = 512) -> Tuple[jax.Array, int]:
    """The ``closure`` backend's rows ``R[v, e] = max_{e_v ∋ v} W*[e_v,
    e]`` [n, m] int32, built on the device, and the rounds the
    propagation ran.  Byte-identical to ``edge_mr_rows(h, mr_matrix(h))``
    and no [m, m] array exists on the host.

    ``stages`` receives ``build.line_graph`` (each hyperedge's member
    list uploaded, the overlap matrix made from it on the device) and
    ``build.rows`` (the propagation to its fixpoint; the span carries
    ``n``, ``m`` and ``rounds``)."""
    stages = stages if stages is not None else Stages("repro")
    n, m = h.n, h.m
    if m == 0:                  # no hyperedges: nothing is reachable
        return jnp.zeros((n, 0), jnp.int32), 0
    with stages.span("build.line_graph", n=n, m=m):
        r0, w = _line_graph(jnp.asarray(_members(h, block)),
                            jnp.asarray(h.edge_sizes, jnp.int32), n=n)
        w.block_until_ready()
    with stages.span("build.rows", n=n, m=m) as span:
        rows, rounds = _rows_fixpoint(r0, w, block=block)
        del r0, w
        rounds = int(rounds)            # waits for the loop to end
        span.set(rounds=rounds)
    return rows, rounds


def vertex_mr_from_edge_mr(h: Hypergraph, w_star: np.ndarray,
                           us: Sequence[int], vs: Sequence[int]) -> np.ndarray:
    """MR(u, v) = max over incident hyperedge pairs of W*."""
    out = np.zeros(len(us), w_star.dtype)
    for q, (u, v) in enumerate(zip(us, vs)):
        eu = h.edges_of(int(u))
        ev = h.edges_of(int(v))
        if eu.size and ev.size:
            out[q] = w_star[np.ix_(eu, ev)].max()
    return out


def mr_oracle_dense(h: Hypergraph) -> np.ndarray:
    """Full vertex-level MR matrix [n, n] (tests on small graphs only)."""
    w_star = mr_matrix(h)
    out = np.zeros((h.n, h.n), w_star.dtype)
    for u in range(h.n):
        eu = h.edges_of(u)
        if not eu.size:
            continue
        rows = w_star[eu, :]                      # [deg(u), m]
        for v in range(h.n):
            ev = h.edges_of(v)
            if ev.size:
                out[u, v] = rows[:, ev].max()
    return out
