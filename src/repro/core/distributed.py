"""Distributed reachability: 2-D block-sharded semiring closures and the
``sharded`` engine backend that serves queries off them.

For hypergraphs whose line graph does not fit one device, the closure
operand R [m, m] is block-sharded over the production mesh axes
``(data, model)`` and each squaring round runs a SUMMA-style contraction
under ``jax.shard_map`` with explicit collectives:

* ``allgather`` schedule — device (i, j) gathers its row panel R[i, :]
  along ``model`` and its column panel R[:, j] along ``data``, then
  contracts locally.  Two all-gathers of m²/P elements per device per
  round; simple, and XLA can overlap the two gathers.
* ``ring`` schedule — the column panel circulates via
  ``jax.lax.ppermute`` while partial contractions accumulate, so each
  step's collective-permute overlaps the previous step's compute
  (the classic Cannon/SUMMA overlap trick).  Same total bytes, but peak
  working set drops from m·m/P_col to m/P_row·m/P_col per step and the
  link traffic is pipelined — this is the collective-bound optimization
  knob for §Perf.

The threshold-batched boolean closure shards its threshold dim over the
``pod`` axis (embarrassingly parallel — zero inter-pod traffic until the
final max-reduce), giving the multi-pod scaling story.

Meshes with unit axes degrade gracefully (the collectives become no-ops),
so the same code runs tests on 1-4 host devices and the 512-way dry-run.

``ShardedEngine`` (registered as backend ``"sharded"`` — see
``repro.core.engine``) wraps these closures in the ``ReachabilityEngine``
protocol: the closure is computed **once** at build time and kept
device-resident in its block-sharded layout; every query — scalar or
batch — is served off that resident structure through a mesh-sharded
``DeviceSnapshot``, never by re-running the closure.  Updates are
**scoped** in both regimes (capability ``"scoped"``): an edge edit
re-closes only the touched line-graph component block and patches the
resident W* / snapshot in place (closure regime), or routes the touched
components through ``build_sharded`` and splices (label regime) — the
full fixpoint and the full pair pass never rerun after build.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import make_mesh, shard_map
from .engine import (WORKLOAD_OPS, _EngineBase, register_backend,
                     validate_batch)
from .hlindex import (HLIndex, auto_device_overlaps, build_sharded,
                      pad_label_rows)
from .hypergraph import (NeighborCSR, apply_edge_edits,
                         induced_subhypergraph, neighbor_csr)
from .maintenance import apply_updates, component_of
from .minimal import minimize
from .query import DeviceSnapshot, mr_query, s_reach_query

__all__ = [
    "pad_for_mesh", "sharded_maxmin_round", "sharded_maxmin_closure",
    "sharded_threshold_closure_mr", "collective_bytes_of",
    "default_line_graph_mesh", "ShardedEngine",
]


def pad_for_mesh(w: np.ndarray, mesh: Mesh,
                 axes: Tuple[str, str] = ("data", "model")) -> np.ndarray:
    """Pad [m, m] (or [S, m, m]) so both block dims divide the mesh axes.
    Zero is the (max,min) annihilator and boolean-adjacency identity, so
    padding is exact for both closure flavors."""
    r, c = mesh.shape[axes[0]], mesh.shape[axes[1]]
    lcm = int(np.lcm(r, c))
    m = w.shape[-1]
    pad = (-m) % lcm
    if pad == 0:
        return w
    widths = [(0, 0)] * (w.ndim - 2) + [(0, pad), (0, pad)]
    return np.pad(w, widths)


def _local_maxmin(a: jax.Array, b: jax.Array, chunk: int = 128) -> jax.Array:
    """Blocked local (max,min) contraction (keeps the broadcast bounded)."""
    m, k = a.shape
    _, n = b.shape
    if k <= chunk:
        return jnp.minimum(a[:, :, None], b[None, :, :]).max(axis=1)
    pad = (-k) % chunk

    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad)))
        b = jnp.pad(b, ((0, pad), (0, 0)))

    def body(carry, kk):
        a_blk = jax.lax.dynamic_slice(a, (0, kk), (m, chunk))
        b_blk = jax.lax.dynamic_slice(b, (kk, 0), (chunk, n))
        c = jnp.minimum(a_blk[:, :, None], b_blk[None, :, :]).max(axis=1)
        return jnp.maximum(carry, c), None

    # init derived from the operands (not a constant) so its device-varying
    # type matches the scan body's output under shard_map
    init = jnp.minimum(a[:, :1], b[:1, :]) * 0
    steps = (k + pad) // chunk
    out, _ = jax.lax.scan(body, init, jnp.arange(steps) * chunk)
    return out


def _local_contraction(use_kernels: bool):
    """The per-device (max,min) contraction inside a closure round:
    the scanned jnp broadcast (default), or the Pallas ``maxmin_matmul``
    kernel (compiled on TPU, interpreted on CPU — ``use_interpret``)
    when the engine was built with ``use_kernels=True``."""
    if not use_kernels:
        return _local_maxmin
    from ..kernels.maxmin_matmul import maxmin_matmul_pallas
    from ..kernels.ops import use_interpret
    interp = use_interpret()
    return functools.partial(maxmin_matmul_pallas, interpret=interp)


def sharded_maxmin_round(mesh: Mesh, *, schedule: str = "allgather",
                         axes: Tuple[str, str] = ("data", "model"),
                         use_kernels: bool = False):
    """Returns a jit-able fn R -> max(R, R∘R) for R sharded P(axes)."""
    row_ax, col_ax = axes
    n_row = mesh.shape[row_ax]
    n_col = mesh.shape[col_ax]
    spec = P(row_ax, col_ax)
    contract = _local_contraction(use_kernels)

    if schedule == "allgather":
        def round_fn(r):
            def body(blk):
                # blk: [m/nr, m/nc] local block at mesh position (i, j)
                row_panel = jax.lax.all_gather(blk, col_ax, axis=1, tiled=True)
                col_panel = jax.lax.all_gather(blk, row_ax, axis=0, tiled=True)
                return jnp.maximum(blk, contract(row_panel, col_panel))
            # pallas_call has no replication rule, so the kernel path
            # must skip the rep check (the body is rep-correct either way)
            return shard_map(body, mesh=mesh, in_specs=spec,
                                 out_specs=spec,
                                 check_vma=not use_kernels)(r)
        return round_fn

    if schedule == "ring":
        def round_fn(r):
            def body(blk):
                # Ring over the row axis: the column panel R[k, j] visits
                # every k; partials accumulate while the next panel is in
                # flight.  Row panel is gathered once along `model`.
                row_panel = jax.lax.all_gather(blk, col_ax, axis=1, tiled=True)
                my_row = jax.lax.axis_index(row_ax)
                perm = [(i, (i + 1) % n_row) for i in range(n_row)]
                block_rows = blk.shape[0]

                def step(carry, t):
                    acc, panel = carry
                    # panel currently holds R[(my_row - t) % n_row, j]
                    src = (my_row - t) % n_row
                    seg = jax.lax.dynamic_slice(
                        row_panel, (0, src * block_rows),
                        (block_rows, block_rows))
                    acc = jnp.maximum(acc, contract(seg, panel))
                    panel = jax.lax.ppermute(panel, row_ax, perm)
                    return (acc, panel), None

                (acc, _), _ = jax.lax.scan(step, (blk, blk),
                                           jnp.arange(n_row))
                return acc
            return shard_map(body, mesh=mesh, in_specs=spec,
                                 out_specs=spec,
                                 check_vma=not use_kernels)(r)
        return round_fn

    raise ValueError(schedule)


def sharded_maxmin_closure(w, mesh: Mesh, *, rounds: Optional[int] = None,
                           schedule: str = "allgather",
                           axes: Tuple[str, str] = ("data", "model"),
                           trim: bool = True, use_kernels: bool = False):
    """Bottleneck closure of a 2-D block-sharded line graph.

    ``w`` is the [m, m] line graph (host or device); the result is W*,
    the hyperedge-level max-reachability matrix.  With ``trim=True``
    (default) the mesh padding is sliced off and the result matches
    ``semiring.maxmin_closure`` exactly.  ``trim=False`` keeps the padded
    [mp, mp] array resident **in its block-sharded layout** — the form
    ``ShardedEngine`` serves queries from (padding entries are zero, the
    (max, min) annihilator, so they never contribute to an answer).
    """
    wp = pad_for_mesh(np.asarray(w), mesh, axes)
    m = wp.shape[0]
    n_rounds = rounds if rounds is not None else max(1, int(np.ceil(np.log2(max(m, 2)))))
    sharding = NamedSharding(mesh, P(*axes))
    r = jax.device_put(jnp.asarray(wp), sharding)
    round_fn = jax.jit(sharded_maxmin_round(mesh, schedule=schedule, axes=axes,
                                            use_kernels=use_kernels))
    for _ in range(n_rounds):
        r = round_fn(r)
    if not trim:
        return r
    return r[:np.asarray(w).shape[0], :np.asarray(w).shape[1]]


def sharded_threshold_closure_mr(w, thresholds, mesh: Mesh, *,
                                 rounds: Optional[int] = None,
                                 axes: Tuple[str, str, str] = ("pod", "data", "model")):
    """MR via threshold-batched boolean closure; thresholds shard over the
    pod axis, each [m, m] slice block-shards over (data, model).  The only
    cross-pod communication is the final max over the threshold dim."""
    pod_ax, row_ax, col_ax = axes
    wn = np.asarray(w)
    m_true = wn.shape[0]
    wp = pad_for_mesh(wn, mesh, (row_ax, col_ax))
    t = np.asarray(thresholds)
    pod = mesh.shape[pod_ax]
    tpad = (-t.size) % pod
    if tpad:
        # repeat the smallest threshold — duplicate slices are harmless
        t = np.concatenate([t, np.full(tpad, t.min(), t.dtype)])
    m = wp.shape[0]
    n_rounds = rounds if rounds is not None else max(1, int(np.ceil(np.log2(max(m, 2)))))

    batch_spec = P(pod_ax, row_ax, col_ax)
    sharding = NamedSharding(mesh, batch_spec)
    adj = (wp[None, :, :] >= t[:, None, None]).astype(np.float32)
    eye = np.eye(m, dtype=np.float32)[None]
    r = jax.device_put(jnp.asarray(np.maximum(adj, eye)), sharding)

    def round_body(blk):
        # blk: [S/pod, m/nr, m/nc]
        row_panel = jax.lax.all_gather(blk, col_ax, axis=2, tiled=True)
        col_panel = jax.lax.all_gather(blk, row_ax, axis=1, tiled=True)
        prod = jax.lax.batch_matmul(row_panel, col_panel)
        return (prod > 0).astype(blk.dtype)

    round_fn = jax.jit(shard_map(round_body, mesh=mesh,
                                     in_specs=batch_spec, out_specs=batch_spec))
    for _ in range(n_rounds):
        r = round_fn(r)
    tj = jnp.asarray(t).astype(jnp.float32)
    mr = (r * tj[:, None, None]).max(axis=0)        # cross-pod max-reduce
    mr = mr.at[jnp.arange(m), jnp.arange(m)].set(jnp.diagonal(jnp.asarray(wp)).astype(jnp.float32))
    return mr[:m_true, :m_true]


def collective_bytes_of(lowered_text: str) -> dict:
    """Sum operand bytes of collectives in an HLO dump — shared helper for
    the roofline harness (single source of truth lives here so both the
    reachability benches and the LM dry-run use identical accounting)."""
    import re
    ops = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
           "collective-permute")
    sizes = dict((k, 0) for k in ops)
    counts = dict((k, 0) for k in ops)
    dtype_bytes = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                   "s8": 1, "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8,
                   "s16": 2, "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    # one HLO instruction per line:  %x = <shape-or-tuple> <opcode>(...)
    line_re = re.compile(
        r"=\s*(\([^)]*\)|\w+\[[\d,]*\](?:\{[^}]*\})?)\s+"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
        r"(-start)?\(")
    for line in lowered_text.splitlines():
        mt = line_re.search(line)
        if not mt:
            continue
        shape_tok, op, _start = mt.groups()
        total = 0
        for d, dd in shape_re.findall(shape_tok):
            if d not in dtype_bytes:
                continue
            n = int(np.prod([int(x) for x in dd.split(",") if x])) if dd else 1
            total += n * dtype_bytes[d]
        sizes[op] += total
        counts[op] += 1
    return {"bytes": sizes, "counts": counts,
            "total_bytes": int(sum(sizes.values()))}


# ---------------------------------------------------------------------------
# The "sharded" engine backend
# ---------------------------------------------------------------------------

def default_line_graph_mesh(axes: Tuple[str, str] = ("data", "model")) -> Mesh:
    """2-D mesh over every visible device, rows × cols as near-square as
    the device count factors (4 -> 2×2, 2 -> 1×2, 1 -> 1×1, 6 -> 2×3).

    Near-square minimizes the allgather panel bytes per device per round
    (row panel m·m/c + column panel m·m/r is minimized at r ≈ c ≈ √P).
    """
    nd = jax.device_count()
    r = max(1, int(np.floor(np.sqrt(nd))))
    while nd % r:
        r -= 1
    return make_mesh((r, nd // r), axes)


def _round_up(x: int, k: int) -> int:
    return -(-x // k) * k


@functools.lru_cache(maxsize=None)
def _closure_patcher(sharding: NamedSharding, donate: bool):
    """Jitted in-place patch of the block-sharded W*: zero the freed
    slots' rows and columns, then scatter the re-closed scope block at
    its slots.  Buffer-donated off CPU, so the resident closure is
    patched without a second [mp, mp] allocation — the same donation
    path ``DeviceSnapshot.to_mesh(donate_base=)`` uses for snapshots."""
    def go(w, freed, slots, sub):
        if freed.shape[0]:
            w = w.at[freed, :].set(0.0)
            w = w.at[:, freed].set(0.0)
        if slots.shape[0]:
            w = w.at[slots[:, None], slots[None, :]].set(sub)
        return w
    return jax.jit(go, out_shardings=sharding,
                   donate_argnums=(0,) if donate else ())


@register_backend("sharded")
class ShardedEngine(_EngineBase):
    """Multi-device backend: W* block-sharded over a mesh, queries served
    off a mesh-sharded ``DeviceSnapshot``.

    Build runs ``sharded_maxmin_closure`` exactly once (allgather or ring
    schedule) and keeps the padded closure resident in its
    ``P(row_axis, col_axis)`` layout.  The snapshot derives the per-vertex
    label rows ``svals[u] = max_{e ∋ u} W*[e, :]`` on device (a scan of
    gathers, output sharded the same way), so label mass never funnels
    through one host round-trip and the snapshot survives across query
    batches.  Same exactness argument as the single-device ``closure``
    backend: every hyperedge is a hub, and the bottleneck triangle
    inequality makes the shared searchsorted join exact on these rows.

    Mesh handling: ``mesh=None`` builds a near-square 2-D mesh over all
    visible devices (``default_line_graph_mesh``); unit axes degrade to
    single-device execution (the collectives become no-ops), so the same
    engine runs on 1 host device and a 16×16 pod slice.

    ``build_labels=True`` switches the backend from the closure regime to
    the **label regime**: instead of keeping W* [m², O(m²/P) per device]
    resident, build runs sharded HL-index construction
    (``repro.core.hlindex.build_sharded`` — neighbor overlaps computed on
    this mesh, per-device component shards, byte-identical to
    ``build_fast``) and serves queries off the mesh-sharded **label**
    snapshot [n·Lmax ≪ m²].  Scalar queries answer through the paper's
    host merge-join.  This is the memory-lean serving shape for graphs
    whose closure no longer fits the mesh.

    **Scoped updates (capability "scoped"), both regimes.**  Labels and
    closure entries never cross line-graph components, so an edit only
    invalidates the component(s) containing its 1-hop touched set:

    * closure regime — hyperedges map to physical W* slots through
      ``_slot_of`` (deletes free slots, inserts take the lowest free
      ones, so W* is never permuted); the (max,min) fixpoint reruns over
      the touched components' sub-line-graph alone and the closed block
      is scattered into the resident W* at its slots (freed slots' rows/
      columns zeroed — every other entry between a scope and non-scope
      slot is already 0, the cross-component annihilator).  The cached
      snapshot is patched row-wise from the same sub-closure
      (``DeviceSnapshot.patch_rows``), so updates stay scoped even after
      ``snapshot()`` dropped W*.
    * label regime — ``apply_updates`` with the engine's persistent
      ``NeighborCSR`` (1-hop patched per edit, never recomputed) and
      ``build_sharded`` as the scope builder: the dirty components run
      LPT-sharded in parallel, then ``splice_rank`` composes exactly as
      serial maintenance — answers byte-identical to a fresh rebuild.

    Both paths report true ``refreshed_vertices`` through the dirty-rows
    contract, so ``ReplicaGroup`` fan-out patches rows instead of
    re-landing snapshots whole.
    """

    name = "sharded"
    update_capability = "scoped"
    # closure/label rows serve the label-row reductions; the host graph
    # is maintained under updates, so the traversal ops run too — same
    # capability shape as the single-device closure backend
    workload_capability = frozenset(WORKLOAD_OPS)
    _gate_hop_bounded = True

    def __init__(self, h, mesh: Mesh, axes: Tuple[str, str],
                 schedule: str, w_star_padded, m_true: int,
                 rounds: Optional[int] = None,
                 idx: Optional[HLIndex] = None,
                 minimizer=None, workers: Optional[int] = None,
                 num_shards: Optional[int] = None,
                 neighbors: Optional[NeighborCSR] = None):
        super().__init__(h)
        self.mesh = mesh
        self.axes = axes
        self.schedule = schedule
        self.rounds = rounds
        self._w_star = w_star_padded       # [mp, mp] sharded P(*axes)
        self._m_padded = (int(w_star_padded.shape[0])
                          if w_star_padded is not None else 0)
        self._m_true = m_true
        self._idx = idx                    # label regime (build_labels=True)
        self._minimizer = minimizer
        self._workers = workers
        self._num_shards = num_shards
        self._nbr = neighbors              # persistent line-graph CSR
        # hyperedge id -> physical W*/snapshot column; identity until a
        # scoped update frees/reuses slots
        self._slot_of = np.arange(m_true, dtype=np.int64)
        # (dirty_vertices, sval rows [d, mp], mp) staged by a scoped
        # closure update for the next snapshot() patch
        self._pending_rows: Optional[Tuple[np.ndarray, np.ndarray, int]] \
            = None
        self._snap: Optional[DeviceSnapshot] = None

    @property
    def build_labels(self) -> bool:
        """True when this engine serves labels instead of the closure."""
        return self._idx is not None

    @staticmethod
    def _closure_of(h, mesh, axes, schedule, rounds, use_kernels=False):
        """(padded sharded W*, m_true) for ``h`` — build and update share
        this so an updated engine is bit-identical to a rebuilt one."""
        if h.m == 0:
            return jnp.zeros((0, 0), jnp.float32), 0
        w = h.line_graph(np.int32).astype(np.float32)
        w_star = sharded_maxmin_closure(w, mesh, rounds=rounds,
                                        schedule=schedule, axes=axes,
                                        trim=False, use_kernels=use_kernels)
        return w_star, h.m

    @classmethod
    def build(cls, h, *, mesh: Optional[Mesh] = None,
              schedule: str = "allgather",
              axes: Optional[Tuple[str, str]] = None,
              rounds: Optional[int] = None,
              build_labels: bool = False,
              minimize_labels: bool = True,
              workers: Optional[int] = None,
              num_shards: Optional[int] = None,
              use_kernels: bool = False) -> "ShardedEngine":
        """``schedule`` ∈ {"allgather", "ring"} picks the collective plan
        (see module docstring); ``rounds`` caps the squaring ladder
        (None = ⌈log2 mp⌉, exact).  ``axes`` names the (row, column) mesh
        axes; None uses the mesh's own last two axis names (so any
        axis naming works), or ``("data", "model")`` when the mesh is
        built here.  ``build_labels=True`` builds the HL-index with
        sharded construction on this mesh instead of the resident
        closure (``minimize_labels`` / ``workers`` / ``num_shards``
        configure it); the closure knobs ``schedule`` / ``rounds`` are
        then unused.  ``use_kernels=True`` runs the per-device closure
        contraction through the Pallas ``maxmin_matmul`` kernel and
        batch queries through the Pallas label join (interpreted on the
        CPU backend; answers byte-identical, conformance-pinned)."""
        if axes is None:
            axes = (("data", "model") if mesh is None
                    else tuple(mesh.axis_names[-2:]))
        if mesh is None:
            mesh = default_line_graph_mesh(axes)
        if len(axes) < 2:
            raise ValueError(
                f"the sharded backend needs a mesh with >= 2 axes to 2-D "
                f"block-shard over; got axis names {mesh.axis_names}")
        if build_labels:
            minimizer = minimize if minimize_labels else None
            # the neighbor index is computed here (same host/mesh route
            # build_sharded would pick) and kept on the engine: scoped
            # updates 1-hop patch it instead of re-running the pair pass
            nbr = neighbor_csr(h, mesh=mesh if (auto_device_overlaps(h)
                               and int(mesh.devices.size) > 1) else None)
            idx = build_sharded(h, mesh=mesh, minimizer=minimizer,
                                workers=workers, num_shards=num_shards,
                                neighbors=nbr)
            eng = cls(h, mesh, axes, schedule, None, h.m, rounds,
                      idx=idx, minimizer=minimizer, workers=workers,
                      num_shards=num_shards, neighbors=nbr)
            eng.use_kernels = bool(use_kernels)
            return eng
        w_star, m_true = cls._closure_of(h, mesh, axes, schedule, rounds,
                                         use_kernels)
        eng = cls(h, mesh, axes, schedule, w_star, m_true, rounds)
        eng.use_kernels = bool(use_kernels)
        return eng

    def _apply_update(self, inserts=(), deletes=()) -> None:
        """Scoped maintenance on the same mesh (capability "scoped"):
        the label regime splices the touched components through the
        parallel sharded builder, the closure regime re-closes only the
        touched block of W* and patches the resident structures in
        place.  See the class docstring for the slot/patch mechanics."""
        if self._idx is not None:
            self._apply_label_update(inserts, deletes)
        else:
            self._apply_closure_update(inserts, deletes)

    def _apply_label_update(self, inserts, deletes) -> None:
        if self._nbr is None:
            # a restored engine lost the build-time neighbor index; pay
            # the pair pass once, then every update 1-hop patches it
            self._nbr = neighbor_csr(self.h)
        builder = functools.partial(build_sharded, workers=self._workers,
                                    num_shards=self._num_shards)
        new_h, self._idx, report = apply_updates(
            self.h, self._idx, inserts, deletes, builder=builder,
            minimizer=self._minimizer, neighbors=self._nbr)
        self._nbr = report.neighbors
        self._m_true = new_h.m
        self._graph_changed(new_h,
                            dirty_rows=(None if report.full_rebuild
                                        else report.refreshed_vertices))

    def _apply_closure_update(self, inserts, deletes) -> None:
        old_h = self.h
        new_h, old_to_new, touched = apply_edge_edits(old_h, inserts,
                                                      deletes)
        scope = (np.fromiter(sorted(component_of(new_h, touched)),
                             np.int64) if touched.size
                 else np.empty(0, np.int64))
        has_basis = self._w_star is not None or self._snap is not None
        if not has_basis or old_h.m == 0 or scope.size == new_h.m:
            # nothing resident to patch, or the edit reaches every
            # hyperedge: recompute whole (identical to a fresh build)
            self._w_star, self._m_true = self._closure_of(
                new_h, self.mesh, self.axes, self.schedule, self.rounds,
                self.use_kernels)
            self._m_padded = int(self._w_star.shape[0])
            self._slot_of = np.arange(new_h.m, dtype=np.int64)
            self._pending_rows = None
            self._graph_changed(new_h)
            return

        # -- slot bookkeeping: survivors keep their physical W* slots,
        # deletions free theirs, inserts take the lowest free slots (so
        # the resident [mp, mp] is never permuted, only patched)
        mp = self._m_padded
        del_ids = np.asarray(sorted({int(d) for d in deletes}), np.int64)
        freed = (self._slot_of[del_ids] if del_ids.size
                 else np.empty(0, np.int64))
        keep = np.nonzero(old_to_new >= 0)[0]
        slot_of = np.empty(new_h.m, np.int64)
        if keep.size:
            slot_of[old_to_new[keep]] = self._slot_of[keep]
        n_new_edges = new_h.m - keep.size
        if n_new_edges:
            used = self._slot_of[keep]
            free = np.setdiff1d(np.arange(mp, dtype=np.int64), used)
            if free.size < n_new_edges:
                lcm = int(np.lcm(self.mesh.shape[self.axes[0]],
                                 self.mesh.shape[self.axes[1]]))
                mp = _round_up(mp + n_new_edges - free.size, lcm)
                self._grow_w_padding(mp)
                free = np.setdiff1d(np.arange(mp, dtype=np.int64), used)
            slot_of[keep.size:] = free[:n_new_edges]
        self._slot_of = slot_of

        # -- re-close only the touched components' block.  Extracting
        # whole components preserves every overlap, and no (max,min)
        # walk crosses a component boundary, so the sub-closure equals
        # the full closure restricted to the scope.
        if scope.size:
            sub_h, sub_verts = induced_subhypergraph(new_h, scope)
            closed = np.asarray(sharded_maxmin_closure(
                sub_h.line_graph(np.int32).astype(np.float32), self.mesh,
                rounds=self.rounds, schedule=self.schedule,
                axes=self.axes, trim=True,
                use_kernels=self.use_kernels), dtype=np.float32)
        else:
            sub_h, sub_verts = None, np.empty(0, np.int64)
            closed = np.zeros((0, 0), np.float32)
        scope_slots = (slot_of[scope] if scope.size
                       else np.empty(0, np.int64))

        # -- patch the resident W* (if still held).  Old entries between
        # a scope slot and a surviving non-scope slot are already 0
        # (different components — insertions only merge components, and
        # every fragment of a deletion-split component contains a
        # surviving touched neighbor of the deleted hyperedge, putting
        # the whole fragment in scope), so zero-freed + scatter-scope is
        # the complete delta.
        if self._w_star is not None and (freed.size or scope.size):
            donate = all(d.platform != "cpu"
                         for d in self.mesh.devices.flat)
            patcher = _closure_patcher(
                NamedSharding(self.mesh, P(*self.axes)), donate)
            self._w_star = patcher(self._w_star,
                                   jnp.asarray(freed, jnp.int32),
                                   jnp.asarray(scope_slots, jnp.int32),
                                   jnp.asarray(closed))

        # -- stage the snapshot row patch: dirty vertices are exactly
        # the scope's vertices plus those of deleted hyperedges (which
        # may have lost their last hyperedge).  Their sval rows come
        # from the sub-closure alone; untouched rows already hold 0 at
        # every slot the patch could change (same confinement argument).
        if self._snap is not None:
            dirty = sub_verts
            if del_ids.size:
                dv = np.unique(np.concatenate(
                    [old_h.edge(int(d)) for d in del_ids]))
                dirty = np.union1d(dirty, dv)
            rows = np.zeros((dirty.size, mp), np.int32)
            if scope.size and sub_verts.size:
                block = np.zeros((sub_verts.size, scope.size), np.float32)
                rr = np.repeat(np.arange(sub_h.n), np.diff(sub_h.v_ptr))
                np.maximum.at(block, rr, closed[sub_h.v_idx])
                pos = np.searchsorted(dirty, sub_verts)
                rows[pos[:, None], scope_slots[None, :]] = \
                    block.astype(np.int32)
            self._merge_pending(dirty.astype(np.int64), rows, mp)
            self._m_true = new_h.m
            self._graph_changed(new_h, dirty_rows=dirty)
        else:
            self._pending_rows = None
            self._m_true = new_h.m
            self._graph_changed(new_h, dirty_rows=None)
            # the fresh W* patch is the whole resident state; the next
            # snapshot() derives from it whole
            self._snap = None

    def _grow_w_padding(self, mp_new: int) -> None:
        """Grow the padded slot space to ``mp_new`` (zero padding is the
        (max,min) annihilator, so growth never changes an answer)."""
        if self._w_star is not None:
            pad = mp_new - self._m_padded
            spec = NamedSharding(self.mesh, P(*self.axes))
            self._w_star = jax.jit(
                lambda w: jnp.pad(w, ((0, pad), (0, pad))),
                out_shardings=spec)(self._w_star)
        self._m_padded = mp_new

    def _merge_pending(self, dirty: np.ndarray, rows: np.ndarray,
                       mp: int) -> None:
        """Accumulate staged snapshot rows across updates between two
        ``snapshot()`` calls.  A previously staged row not re-dirtied by
        this update is still valid: any slot this update changed that
        could intersect it would have pulled its component into this
        update's scope (and hence re-dirtied it), so its value there was
        already 0 — only zero-padding to the grown width is needed."""
        prev = self._pending_rows
        if prev is not None:
            pd, prows, pmp = prev
            stale = ~np.isin(pd, dirty)
            if stale.any():
                old_rows = np.zeros((int(stale.sum()), mp), np.int32)
                old_rows[:, :pmp] = prows[stale]
                dirty = np.concatenate([dirty, pd[stale]])
                rows = np.concatenate([rows, old_rows])
                order = np.argsort(dirty)
                dirty, rows = dirty[order], rows[order]
        self._pending_rows = (dirty, rows, mp)

    # -- queries: everything routes through the resident snapshot (label
    # regime scalars short-circuit to the paper's host merge-join) -------

    def mr(self, u: int, v: int) -> int:
        if self._idx is not None:
            # the closure regime validates scalars through the batch
            # path; the label short-circuit rejects the same inputs
            self._check_vertex_ids(u, v)
            return mr_query(self._idx, int(u), int(v))
        return int(self.mr_batch(np.array([int(u)]), np.array([int(v)]))[0])

    def s_reach(self, u: int, v: int, s: int) -> bool:
        if self._idx is not None:
            self._check_vertex_ids(u, v)
            return s_reach_query(self._idx, int(u), int(v), int(s))
        return self.mr(u, v) >= int(s)

    def mr_batch(self, us, vs) -> np.ndarray:
        us, vs = validate_batch(us, vs, self.h.n)
        return np.asarray(self._query_snapshot().mr(us, vs)).astype(np.int64)

    def s_reach_batch(self, us, vs, s: int) -> np.ndarray:
        us, vs = validate_batch(us, vs, self.h.n)
        return np.asarray(self._query_snapshot().s_reach(us, vs, int(s)))

    def snapshot(self) -> DeviceSnapshot:
        """Current padded device form.  After a scoped update the stale
        snapshot is **patched**: only the dirty rows are re-derived (from
        the spliced labels, or from the staged sub-closure rows) and
        scattered over the old tensors.  Only a full re-derivation frees
        W*, and only while no WAL is attached — with an ``IndexStore`` in
        front, more updates are coming and the resident closure is what
        keeps them patchable in place, so it is retained."""
        if self._snapshot_current():
            return self._snap
        basis, dirty = self._snap, self._dirty_rows
        if self._idx is not None and basis is not None and dirty is not None:
            self._snap = self._patched_label_snapshot(basis, dirty)
            self.last_snapshot_refresh_rows = int(np.asarray(dirty).size)
        elif (basis is not None and dirty is not None
                and self._pending_rows is not None):
            self._snap = self._patched_closure_snapshot(basis)
            self.last_snapshot_refresh_rows = int(self._pending_rows[0].size)
        else:
            self._snap = self._build_snapshot()
            self.last_snapshot_refresh_rows = self.h.n
            if self._idx is None and self._wal is None:
                # static serving: every query path serves off the
                # snapshot from here on — free the closure so the
                # resident footprint is the snapshot alone (scoped
                # updates still work: they patch the snapshot directly)
                self._w_star = None
        self._pending_rows = None
        self._dirty_rows = np.empty(0, np.int64)
        return self._snap

    def _slot_ceiling(self) -> int:
        """Number of leading snapshot columns that can carry a live
        hyperedge (max occupied slot + 1) — the row ``lengths`` bound.
        Identity slots make this ``m_true``, matching a fresh build."""
        return int(self._slot_of.max()) + 1 if self._slot_of.size else 0

    def _patched_closure_snapshot(self, basis: DeviceSnapshot
                                  ) -> DeviceSnapshot:
        dirty, rows, mp = self._pending_rows
        cur_l = int(basis.ranks.shape[1])
        lmax = max(cur_l, mp)
        if rows.shape[1] < lmax:
            rows = np.pad(rows, ((0, 0), (0, lmax - rows.shape[1])))
        n_eff = max(int(basis.ranks.shape[0]),
                    _round_up(self.h.n, self.mesh.shape[self.axes[0]]))
        # rank space = slot id, dense ascending per row (same form the
        # full derivation materializes); untouched rows keep theirs
        row_ranks = np.broadcast_to(np.arange(lmax, dtype=np.int32),
                                    (dirty.size, lmax))
        row_lengths = np.full(dirty.size, self._slot_ceiling(), np.int32)
        return basis.patch_rows(dirty, row_ranks, rows, row_lengths,
                                n=n_eff, lmax=lmax, version=self.version,
                                backend=self.name)

    def _patched_label_snapshot(self, basis: DeviceSnapshot,
                                dirty) -> DeviceSnapshot:
        idx = self._idx
        dirty = np.asarray(dirty, np.int64)
        basis_len = np.asarray(basis.lengths)
        dirty_len = [idx.labels_s[int(u)].size for u in dirty]
        lmax = int(max(int(basis_len.max()) if basis_len.size else 0,
                       max(dirty_len, default=0)))
        row_ranks, row_svals, row_lengths = pad_label_rows(
            [idx.labels_rank[int(u)] for u in dirty],
            [idx.labels_s[int(u)] for u in dirty], pad_to=lmax)
        n_eff = max(int(basis.ranks.shape[0]), self.h.n)
        return basis.patch_rows(dirty, row_ranks, row_svals, row_lengths,
                                n=n_eff, lmax=lmax, version=self.version,
                                backend=self.name)

    def _build_snapshot(self) -> DeviceSnapshot:
        h, mesh = self.h, self.mesh
        row_ax, col_ax = self.axes
        if self._idx is not None:
            snap = DeviceSnapshot.from_hlindex(self._idx, self.name,
                                               version=self.version)
            if h.n == 0 or snap.lmax == 0:
                return snap            # nothing to shard over the mesh
            return snap.to_mesh(mesh, self.axes)
        if self._m_true == 0 or h.n == 0:
            z = np.zeros((h.n, 0), np.int32)
            return DeviceSnapshot.from_padded(z, z, np.zeros(h.n, np.int32),
                                              self.name, version=self.version)
        mp = self._m_padded
        n_pad = _round_up(h.n, mesh.shape[row_ax])
        deg = np.diff(h.v_ptr)
        d_max = max(int(deg.max()), 1)
        # padded incidence: inc[u, k] = k-th hyperedge of u, mp = phantom
        # all-zero row of the padded closure (annihilator => no-op);
        # one-shot scatter straight from the CSR arrays
        inc = np.full((n_pad, d_max), mp, np.int32)
        rows = np.repeat(np.arange(h.n), deg)
        cols = np.arange(h.nnz) - np.repeat(h.v_ptr[:-1], deg)
        inc[rows, cols] = self._slot_of[h.v_idx]   # edge id -> W* slot
        spec2d = NamedSharding(mesh, P(row_ax, col_ax))
        inc_dev = jax.device_put(inc, NamedSharding(mesh, P(row_ax, None)))

        @functools.partial(jax.jit, out_shardings=spec2d)
        def vertex_rows(w_star, inc):
            # svals[u] = max_{e in E(u)} W*[e, :], scanned over the degree
            # dim so the working set stays one [n_pad, mp] panel
            w1 = jnp.concatenate(
                [w_star, jnp.zeros((1, w_star.shape[1]), w_star.dtype)], 0)

            def body(acc, d):
                return jnp.maximum(acc, w1[jnp.take(inc, d, axis=1)]), None

            init = jnp.zeros((inc.shape[0], w_star.shape[1]), w_star.dtype)
            out, _ = jax.lax.scan(body, init, jnp.arange(inc.shape[1]))
            return out

        svals = vertex_rows(self._w_star, inc_dev).astype(jnp.int32)
        # rank space = hyperedge id (ascending per row by construction);
        # padded columns carry sval 0, which can never win the join max.
        # Materialized directly on device in the sharded layout — the
        # [n_pad, mp] broadcast never exists on the host.
        ranks = jax.jit(
            lambda: jnp.broadcast_to(jnp.arange(mp, dtype=jnp.int32),
                                     (n_pad, mp)),
            out_shardings=spec2d)()
        lengths = np.zeros(n_pad, np.int32)
        # every occupied slot must fall inside the row length; identity
        # slots make this m_true, same as before scoped maintenance
        lengths[:h.n] = self._slot_ceiling()
        lengths = jax.device_put(lengths, NamedSharding(mesh, P(row_ax)))
        return DeviceSnapshot.from_padded(ranks, svals, lengths, self.name,
                                          version=self.version)

    def block_until_built(self) -> None:
        if self._w_star is not None:
            jax.block_until_ready(self._w_star)

    def nbytes(self) -> int:
        total = 0
        if self._w_star is not None:
            total += self._m_padded * self._m_padded * 4
        if self._idx is not None:
            total += self._idx.nbytes()
        if self._nbr is not None:
            total += self._nbr.nbytes()
        if self._snap is not None:
            total += self._snap.nbytes()
        return total
