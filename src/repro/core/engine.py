"""Unified reachability engine API: one query surface, pluggable backends.

The repo ships several ways to answer the paper's two query problems —
``MR(u, v)`` (Problem 2, Algorithm 5) and ``u ~s~> v`` (Problem 1) — each
grown with its own build/query signature: the HL-index merge-join
(query.py), the padded JAX batch engine (``PaddedIndex``), the sparse
line-graph frontier sweeps (frontier.py), the online bidirectional search
(online.py), and the baseline oracles (baselines.py).  This module folds
them all behind one protocol:

    engine = build(h, backend="hl-index")     # or "auto"
    engine.mr(u, v)                           # scalar MR
    engine.s_reach(u, v, s)                   # scalar s-reachability
    engine.mr_batch(us, vs)                   # [Q] MR, vectorized
    engine.s_reach_batch(us, vs, s)           # [Q] bool
    engine.snapshot()                         # device-resident padded form

Backends register themselves under a string key (``register_backend``);
``build(h, backend="auto")`` consults a planner that picks a backend from
the graph size, the label mass, the expected query batch shape, and —
when a ``mesh`` is passed — the device topology (a multi-device mesh
whose line-graph closure exceeds the single-device budget routes to the
``sharded`` backend).  Adding a new structure (a HypED-style threshold
oracle, a sharded device engine, ...) is one registry entry — not a new
public API.  The full backend catalogue, the planner policy, and the
data-flow picture live in ``docs/ARCHITECTURE.md``.

``DeviceSnapshot`` generalizes ``HLIndex.as_padded``: any backend that can
express its structure as per-vertex sorted (hub, s) label rows exports the
same padded tensors, and every snapshot is served by the same fused
``batched_mr`` join.  Backends with no label form (online search, frontier
sweeps, union-find components, the MST forest) raise
``SnapshotUnsupported`` — their batch paths run through their own engines.

Hyperedge updates go through the same protocol: ``engine.update(inserts,
deletes)`` mutates the engine in place to serve the edited graph.  Each
backend declares how via its ``update_capability`` class attribute
(surfaced by ``update_capabilities()`` and CI-checked against the table
in docs/ARCHITECTURE.md):

* ``"scoped"`` — construction reruns only on the affected line-graph
  component(s) and is spliced into the surviving structure
  (``hl-index``, ``hl-index-basic`` via ``repro.core.maintenance``);
* ``"incremental"`` — adjacency caches are patched on the 1-hop touched
  set, no construction at all (``online``, ``frontier``);
* ``"rebuild"`` — the structure is recomputed whole, but through the
  same call so serving code never special-cases it (``closure``;
  ``sharded`` graduated to "scoped" — its closure regime re-closes only
  the touched component block of W*, its label regime splices through
  the parallel sharded builder);
* ``"unsupported"`` — ``update`` raises ``UpdateUnsupported`` (the
  static baselines: ``ete``, ``threshold``, ``mst-oracle``).

Every successful update bumps ``engine.version`` and invalidates the
cached ``DeviceSnapshot`` — snapshots carry the version they were
derived from, so staleness is detectable even after ``to_mesh``.

Snapshot *caching* rides on the same versioning: engines keep the stale
snapshot as a patch basis and track the dirty label rows each update
touched (``dirty_rows()``; fed by the scoped-maintenance
``UpdateReport`` for the HL-index backends), so ``snapshot()`` after a
scoped update re-derives only the changed rows via
``DeviceSnapshot.patch_rows`` — byte-identical to a from-scratch
derivation, asserted in tests.  ``last_snapshot_refresh_rows`` records
how many rows the most recent ``snapshot()`` actually re-derived.  The
request-based serving layer (``repro.serve.reach_service``) consumes
exactly this contract to swap snapshots between micro-batches.
"""
from __future__ import annotations

import functools
import os
from typing import (TYPE_CHECKING, Callable, Dict, FrozenSet, List,
                    Optional, Protocol, Tuple, runtime_checkable)

import numpy as np

if TYPE_CHECKING:                      # annotation-only; the workload
    # package imports this module, so the runtime imports are lazy
    from repro.workloads.base import Witness
    from repro.workloads.oracle import DistanceOracle

from .hypergraph import Hypergraph, apply_edge_edits
from .hlindex import (CONSTRUCTION_MODES, HLIndex, build_basic, build_fast,
                      build_sharded, pad_label_rows)
from .minimal import minimize
from .maintenance import apply_updates, normalize_update_batch
from .query import DeviceSnapshot, KernelSnapshot, mr_query, s_reach_query
from .online import NeighborCache, mr_online
from .frontier import (SparseLineGraph, frontier_batched_mr,
                       frontier_batched_s_reach)
from .baselines import (ETEIndex, MSTOracle, ThresholdComponentIndex,
                        build_ete)
from .semiring import vertex_rows
from ..stages import Stages

__all__ = [
    "ReachabilityEngine", "DeviceSnapshot", "KernelSnapshot",
    "SnapshotUnsupported",
    "UpdateUnsupported", "WorkloadUnsupported", "WORKLOAD_OPS",
    "register_backend", "available_backends",
    "update_capabilities", "workload_capabilities", "plan_backend",
    "build", "validate_batch",
    "HLIndexEngine", "OnlineEngine", "FrontierEngine", "ETEEngine",
    "ThresholdEngine", "MSTOracleEngine", "ClosureEngine",
    "SINGLE_DEVICE_CLOSURE_BUDGET", "CLOSURE_JOIN_BATCH",
    "CLOSURE_BUILD_BUDGET", "closure_build_pairs",
    "closure_working_set_bytes", "device_memory_budget",
    "CONSTRUCTION_MODES",
]


def validate_batch(us, vs, n: int):
    """Shared input validation for every backend's ``mr_batch`` /
    ``s_reach_batch`` (and the serving layer's admission path): ``us`` /
    ``vs`` must be equal-length 1-D integer sequences of in-range vertex
    ids.  Returns them as int64 numpy arrays.  Before this helper,
    malformed input failed differently per backend (silent wraparound,
    shape broadcast errors deep inside jitted code, ...); now every
    entry point raises the same clear error.
    """
    us = np.asarray(us)
    vs = np.asarray(vs)
    if us.ndim != 1 or vs.ndim != 1:
        raise ValueError(
            f"query batch must be 1-D sequences of vertex ids; got shapes "
            f"us{us.shape} vs{vs.shape}")
    if us.shape[0] != vs.shape[0]:
        raise ValueError(
            f"query batch length mismatch: len(us)={us.shape[0]} != "
            f"len(vs)={vs.shape[0]}")
    for name, a in (("us", us), ("vs", vs)):
        if a.size and not np.issubdtype(a.dtype, np.integer):
            raise ValueError(
                f"query batch {name} must have an integer dtype; got "
                f"{a.dtype}")
    us = us.astype(np.int64)
    vs = vs.astype(np.int64)
    for name, a in (("us", us), ("vs", vs)):
        if a.size and (int(a.min()) < 0 or int(a.max()) >= n):
            bad = int(a.min()) if int(a.min()) < 0 else int(a.max())
            raise IndexError(
                f"query batch {name} contains vertex id {bad}, out of "
                f"range [0, {n})")
    return us, vs

# Per-device byte budget for the dense closure working set (operand plus
# the two gathered panels, f32).  When a multi-device mesh is passed and
# 12·m² exceeds this, the auto planner routes to the "sharded" backend.
# The single-device closure route reads the device's own memory instead,
# and falls back to this where the device reports none (the CPU).
SINGLE_DEVICE_CLOSURE_BUDGET = 256 * 2**20

# Query batch the closure route reserves join room for when the caller
# names none: the service's default ``max_batch``.
CLOSURE_JOIN_BATCH = 4096

# (max, min) pair operations the single-device closure route may spend
# building its rows: about 20 s of set-up on one TPU v5e, which runs a
# 6.6e11-pair round of email-Eu's rows in 0.66 s.
CLOSURE_BUILD_BUDGET = 2e13


def closure_working_set_bytes(n: int, m: int, join_batch: int) -> int:
    """Device bytes of the ``closure`` regime, its parts summed: the
    overlap matrix (int32 [m, m]), the rows and their snapshot keys (two
    int32 [n, m]), and one join of ``join_batch`` queries (keys and
    s-values of both gathered rows, and their sorted copies: four int32
    [Q, 2m])."""
    return 4 * m * m + 8 * n * m + 32 * join_batch * m


def closure_build_pairs(n: int, m: int) -> int:
    """Estimated (max, min) pair operations of the ``closure`` rows
    build: n·m² a round, for ⌈log2 m⌉ + 1 rounds (the hops of a
    small-world graph's longest optimal walk, plus the round that
    changes nothing).  An n ≈ m graph costs m³ a round and fails the
    budget where an n ≪ m one passes it."""
    return n * m * m * (max(int(m - 1).bit_length(), 1) + 1)


def device_memory_budget() -> int:
    """Bytes the single-device closure route may plan for: three
    quarters of what the default device's ``memory_stats()`` reports as
    its limit, or ``SINGLE_DEVICE_CLOSURE_BUDGET`` where it reports
    none."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    limit = int(stats.get("bytes_limit", 0))
    return limit * 3 // 4 if limit else SINGLE_DEVICE_CLOSURE_BUDGET


class SnapshotUnsupported(NotImplementedError):
    """Raised by backends whose structure has no padded label form."""


class UpdateUnsupported(NotImplementedError):
    """Raised by backends whose structure cannot absorb hyperedge
    updates (``update_capability == "unsupported"``) — rebuild the
    engine via ``build`` instead."""


class WorkloadUnsupported(NotImplementedError):
    """Raised by backends that do not serve a workload op (witness /
    s_reach_k / mr_set / top_s / s_distance) — see
    ``workload_capabilities()`` and the capability table in
    docs/ARCHITECTURE.md."""


# canonical workload-op order; docs check 9 and the conformance matrix
# both pin their tables against exactly this tuple
WORKLOAD_OPS: Tuple[str, ...] = ("witness", "s_reach_k", "mr_set",
                                 "top_s", "s_distance")

# capability rule (per backend below): the label-row reductions —
# witness (hub named by the label join), mr_set, top_s — need a
# snapshot-capable label/closure form; the traversal ops — s_reach_k,
# s_distance — need a graph the backend keeps live under updates.  The
# static Section IV/VII baselines (threshold, mst-oracle) serve the
# paper's two problems only.
_LABEL_OPS = frozenset({"witness", "mr_set", "top_s"})
_TRAVERSAL_OPS = frozenset({"s_reach_k", "s_distance"})


# ---------------------------------------------------------------------------
# Protocol + shared scaffolding
# ---------------------------------------------------------------------------

@runtime_checkable
class ReachabilityEngine(Protocol):
    """The one query surface every backend serves.

    Semantics (fixed across backends, cross-validated against the
    ``mst-oracle`` reference in tests and benchmarks):

    * ``mr(u, v)`` — Problem 2: the largest ``s`` such that an s-walk
      joins vertices ``u`` and ``v``.  0 means unreachable at every
      ``s >= 1``; for ``u == v`` it is the max incident hyperedge size
      (a vertex trivially reaches itself through any incident edge).
      Vertices with no incident hyperedge answer 0 everywhere.
    * ``s_reach(u, v, s)`` — Problem 1: is there an s-walk joining
      ``u`` and ``v``?  Always equals ``mr(u, v) >= s``.
    * ``mr_batch(us, vs) -> int array [Q]`` / ``s_reach_batch(us, vs, s)
      -> bool array [Q]`` — vectorized forms; ``us``/``vs`` are equal
      length sequences of vertex ids.
    * ``snapshot() -> DeviceSnapshot`` — the padded device-resident label
      form (see ``repro.core.query``), or raises ``SnapshotUnsupported``
      for structures with no label form (online search, frontier sweeps,
      union-find components, the MST forest).
    * ``update(inserts, deletes)`` — mutate the engine in place so it
      serves the edited hypergraph (semantics identical to rebuilding
      from scratch, asserted in tests), or raise ``UpdateUnsupported``.
      ``update_capability`` ∈ {"scoped", "incremental", "rebuild",
      "unsupported"} declares how; ``version`` counts successful updates
      so snapshot staleness is detectable.

    Workload ops (``src/repro/workloads/``) — each gated by
    ``workload_capability`` (the set of ``WORKLOAD_OPS`` the backend
    serves; anything else raises ``WorkloadUnsupported``), each pinned
    against the brute-force references in ``core.baselines``:

    * ``mr_witness(u, v) -> Witness`` — the MR answer plus the
      hyperedge walk achieving it.
    * ``s_reach_k(u, v, s, k) -> bool`` — an s-walk of at most ``k``
      hyperedges exists.
    * ``mr_set(us, vs) -> int`` / ``mr_from_set(us, targets) ->
      int array`` — set-to-set / multi-source MR reductions.
    * ``top_s(u, k) -> (vertices, mr values)`` — the k strongest
      targets of ``u``, ranked (MR desc, id asc), zeros dropped.
    * ``s_distance(u, v, s) -> int`` — certified upper bound on the
      s-distance in hyperedges (0 = provably no s-walk), served off the
      cached per-``s`` ``distance_oracle(s)`` landmark structure.
    """

    name: str
    update_capability: str
    workload_capability: FrozenSet[str]

    def mr(self, u: int, v: int) -> int: ...
    def s_reach(self, u: int, v: int, s: int) -> bool: ...
    def mr_batch(self, us, vs) -> np.ndarray: ...
    def s_reach_batch(self, us, vs, s: int) -> np.ndarray: ...
    def snapshot(self) -> DeviceSnapshot: ...
    def update(self, inserts=(), deletes=()) -> None: ...
    def mr_witness(self, u: int, v: int) -> "Witness": ...
    def s_reach_k(self, u: int, v: int, s: int, k: int) -> bool: ...
    def mr_set(self, us, vs) -> int: ...
    def mr_from_set(self, us, targets) -> np.ndarray: ...
    def top_s(self, u: int, k: int) -> Tuple[np.ndarray, np.ndarray]: ...
    def s_distance(self, u: int, v: int, s: int) -> int: ...


class _EngineBase:
    """Default implementations: scalar fallbacks and mr-derived s-reach.

    Backends override whichever paths their structure accelerates; the
    semantics (``s_reach(u, v, s) == (mr(u, v) >= s)``) are fixed here so
    every backend answers identically.
    """

    name = "base"
    update_capability = "unsupported"
    # which WORKLOAD_OPS this backend serves (see the rule above the
    # registry); empty = the paper's two problems only
    workload_capability: FrozenSet[str] = frozenset()
    # index lookups cheap enough that s_reach_k pre-gates the bounded
    # BFS on an unbounded reachability answer (label join / closure
    # row); False where s_reach is itself a traversal
    _gate_hop_bounded = False

    def __init__(self, h: Hypergraph):
        self.h = h
        self.version = 0
        # label rows changed since the cached snapshot was derived:
        # empty = snapshot current / patchable as-is, None = all rows
        # (unknown or whole-structure rebuild)
        self._dirty_rows: Optional[np.ndarray] = np.empty(0, np.int64)
        self.last_snapshot_refresh_rows = 0
        # write-ahead sink (repro.store): None = updates are not journaled
        self._wal = None
        # kernel-path batch queries (Pallas label join); flipped by the
        # snapshot-serving backends' ``build(use_kernels=True)``
        self.use_kernels = False
        self._kernel_view: Optional[KernelSnapshot] = None
        # per-(s, extra_landmarks) DistanceOracle cache; invalidated on
        # every graph change (_graph_changed)
        self._distance_oracles: Dict[Tuple[int, int], "DistanceOracle"] = {}
        # construction stage totals (``repro.build`` spans), filled by
        # the backends whose ``build`` times its stages
        self.build_stages = Stages("repro")

    @classmethod
    def build(cls, h: Hypergraph, **opts) -> "ReachabilityEngine":
        raise NotImplementedError

    def mr(self, u: int, v: int) -> int:
        raise NotImplementedError

    def _check_vertex_ids(self, *ids) -> None:
        """Scalar-path counterpart of ``validate_batch``: backends whose
        ``mr`` / ``s_reach`` index host structures directly call this
        first, so an out-of-range id raises the same ``IndexError`` as
        the batch paths instead of a Python negative index silently
        answering from the wrong row."""
        for x in ids:
            if not 0 <= int(x) < self.h.n:
                raise IndexError(
                    f"vertex id {int(x)} out of range [0, {self.h.n})")

    def update(self, inserts=(), deletes=()) -> None:
        """Template method every backend shares: gate on capability,
        validate + canonicalize the batch, journal it durably (when a
        WAL sink is attached — fsync *before* the in-memory structure
        changes), then hand the canonical batch to the backend's
        ``_apply_update``.  Ordering matters: a batch that would be
        rejected is never journaled, and a journaled batch is replayed
        byte-identically on restart (``repro.store``)."""
        if self.update_capability == "unsupported":
            raise UpdateUnsupported(
                f"backend {self.name!r} does not maintain its structure "
                f"under hyperedge updates; build a fresh engine instead")
        ins, dels = normalize_update_batch(self.h, inserts, deletes)
        wal = self._wal
        if wal is not None:
            wal.append(self.version + 1, ins, dels)
        self._apply_update(ins, dels)
        if wal is not None:
            wal.committed(self)

    def _apply_update(self, inserts, deletes) -> None:
        """Backend hook behind ``update``: mutate the structure in place
        for an already-validated, canonical batch and call
        ``_graph_changed``.  Only backends whose ``update_capability``
        is not ``"unsupported"`` are ever called here."""
        raise UpdateUnsupported(
            f"backend {self.name!r} declares update_capability="
            f"{self.update_capability!r} but implements no _apply_update")

    def attach_wal(self, sink) -> None:
        """Journal every subsequent ``update`` through ``sink`` — any
        object with ``append(version, inserts, deletes)`` (durable,
        called before the apply) and ``committed(engine)`` (called
        after); ``repro.store.WriteAheadLog`` and ``IndexStore`` both
        qualify."""
        self._wal = sink

    def detach_wal(self):
        """Stop journaling; returns the detached sink (the store's
        replay path detaches around ``update`` so replayed records are
        not re-journaled)."""
        sink, self._wal = self._wal, None
        return sink

    def _graph_changed(self, new_h: Hypergraph, dirty_rows=None) -> None:
        """Install the edited graph and bump ``version``.  ``dirty_rows``
        names the label rows the update changed (accumulated across
        updates): the cached snapshot becomes stale but is *kept* as the
        patch basis for the next ``snapshot()``.  ``None`` means all
        rows — the next derivation is full anyway, so the stale snapshot
        is dropped immediately rather than held through the rebuild
        (rebuild-capability backends and the full-rebuild fallbacks of
        scoped ones are the memory-bound regime; holding an unusable
        snapshot across ``update`` would raise peak memory for
        nothing)."""
        self.h = new_h
        self.version += 1
        self._distance_oracles.clear()   # landmark BFS trees are per-graph
        if dirty_rows is None:
            self._dirty_rows = None
            if getattr(self, "_snap", None) is not None:
                self._snap = None
        elif self._dirty_rows is not None:
            self._dirty_rows = np.union1d(
                self._dirty_rows, np.asarray(dirty_rows, np.int64))

    def dirty_rows(self) -> Optional[np.ndarray]:
        """Vertex rows whose padded label content may differ between the
        cached (stale) snapshot — ``snapshot_cache()`` — and the one the
        next ``snapshot()`` call returns; ``None`` = all rows / unknown.
        Resets to empty once ``snapshot()`` re-derives.  The serving
        layer reads this *before* refreshing to patch mesh-resident
        snapshot copies row-wise; the delta is only meaningful relative
        to ``snapshot_cache()``, so consumers holding an older copy must
        check identity against it first."""
        return self._dirty_rows

    def snapshot_cache(self) -> Optional[DeviceSnapshot]:
        """The currently cached snapshot object (possibly stale), or
        ``None``.  ``dirty_rows()`` is the row delta between exactly
        this object and the next ``snapshot()`` result — consumers that
        patch their own resident copies row-wise must confirm their copy
        derives from this object before trusting the delta."""
        return getattr(self, "_snap", None)

    def _snapshot_current(self) -> bool:
        snap = getattr(self, "_snap", None)
        return snap is not None and snap.version == self.version

    def snapshot_delta(self, basis: Optional[DeviceSnapshot] = None,
                       ) -> Tuple[DeviceSnapshot, Optional[np.ndarray]]:
        """The snapshot fan-out hook: one call returning ``(fresh
        snapshot, dirty-row delta relative to basis)`` — what a consumer
        holding device-resident copies landed from ``basis`` needs to
        bring *all* of them current with row-wise patches instead of
        full re-lands (``to_mesh(base=, dirty_rows=)``).

        ``basis`` is the host snapshot the caller's copies derive from.
        The delta is ``None`` (re-land in full) when it is unknowable:
        no basis, the basis is not the engine's cached snapshot object
        (another consumer re-derived in between, resetting the delta),
        or the update was a whole-structure rebuild.  The dirty set must
        be captured *before* ``snapshot()`` re-derives and resets it,
        which is exactly the ordering this method encapsulates — the
        serving layer and ``ReplicaGroup`` both build on it.  Raises
        ``SnapshotUnsupported`` for backends with no snapshot form."""
        dirty = (self.dirty_rows()
                 if basis is not None and self.snapshot_cache() is basis
                 else None)
        snap = self.snapshot()
        if snap is basis:
            dirty = np.empty(0, np.int64)      # already current: patch nothing
        return snap, dirty

    def _query_snapshot(self):
        """The snapshot view batch queries run through: the plain
        ``DeviceSnapshot`` (XLA ``batched_mr``), or — with
        ``use_kernels`` — a cached ``KernelSnapshot`` wrapper that
        answers through the Pallas label-join kernel.  The wrapper is
        rebuilt whenever ``snapshot()`` hands back a different object
        (update / patch / re-derivation), so it can never serve stale
        label rows."""
        snap = self.snapshot()
        if not self.use_kernels:
            return snap
        kv = self._kernel_view
        if kv is None or kv.base is not snap:
            kv = KernelSnapshot(snap)
            self._kernel_view = kv
        return kv

    def s_reach(self, u: int, v: int, s: int) -> bool:
        return self.mr(u, v) >= s

    def mr_batch(self, us, vs) -> np.ndarray:
        us, vs = validate_batch(us, vs, self.h.n)
        return np.array([self.mr(int(u), int(v)) for u, v in zip(us, vs)],
                        np.int64)

    def s_reach_batch(self, us, vs, s: int) -> np.ndarray:
        return self.mr_batch(us, vs) >= s

    def snapshot(self) -> DeviceSnapshot:
        raise SnapshotUnsupported(
            f"backend {self.name!r} has no padded device form; query it "
            f"through mr_batch / s_reach_batch instead")

    # -- workload ops (src/repro/workloads/) -------------------------------

    def _require_workload(self, op: str) -> None:
        if op not in self.workload_capability:
            raise WorkloadUnsupported(
                f"backend {self.name!r} does not serve workload op "
                f"{op!r}; see workload_capabilities()")

    def _witness_hub(self, u: int, v: int, k: int) -> Optional[int]:
        """The hyperedge the label join met at, when the backend's
        structure names one (HL-index labels); None lets the extractor
        meet wherever the frontiers touch (closure backends, where
        every hyperedge is a hub)."""
        return None

    def mr_witness(self, u: int, v: int) -> "Witness":
        """MR(u, v) plus the hyperedge walk achieving it (hub-anchored
        meet-in-the-middle reconstruction; ``verify_witness`` checks
        the result from the hypergraph alone)."""
        self._require_workload("witness")
        from repro.workloads.base import Witness
        from repro.workloads.witness import extract_witness
        self._check_vertex_ids(u, v)
        u, v = int(u), int(v)
        k = int(self.mr(u, v))
        walk = (extract_witness(self.h, u, v, k,
                                hub=self._witness_hub(u, v, k))
                if k > 0 else ())
        return Witness(u=u, v=v, s=k, walk=tuple(int(e) for e in walk))

    def s_reach_k(self, u: int, v: int, s: int, k: int) -> bool:
        """Hop-bounded s-reach: an s-walk of at most ``k`` hyperedges.
        Index-backed engines pre-gate the bounded search: unbounded
        unreachable rejects immediately, and ``k >= m`` accepts
        immediately (shortest s-walks never repeat a hyperedge)."""
        self._require_workload("s_reach_k")
        self._check_vertex_ids(u, v)
        u, v, s, k = int(u), int(v), int(s), int(k)
        if s < 1:
            raise ValueError(f"s-reachability needs s >= 1; got {s}")
        if k < 1:
            raise ValueError(f"hop bound needs k >= 1; got {k}")
        if self._gate_hop_bounded:
            if not self.s_reach(u, v, s):
                return False             # early-reject: no walk at all
            if k >= self.h.m:
                return True              # early-accept: m edges suffice
        return self._bounded_s_reach(u, v, s, k)

    def _bounded_s_reach(self, u: int, v: int, s: int, k: int) -> bool:
        """Backend hook behind the gate: host bounded BFS by default;
        the frontier backend swaps in its jitted sweep."""
        from repro.workloads.hop_bounded import hop_bounded_s_reach
        return bool(hop_bounded_s_reach(self.h, u, v, s, k))

    def mr_set(self, us, vs) -> int:
        """Set-to-set MR: ``max over U x V of MR(u, v)``, answered as
        one cross-product batch through ``mr_batch`` — the vectorized
        snapshot join, kernel-path eligible like any other batch."""
        self._require_workload("mr_set")
        from repro.workloads.setops import cross_pairs, normalize_vertex_set
        sources = normalize_vertex_set(us, self.h.n, "mr_set source set")
        targets = normalize_vertex_set(vs, self.h.n, "mr_set target set")
        qu, qv = cross_pairs(sources, targets)
        return int(np.asarray(self.mr_batch(qu, qv)).max())

    def mr_from_set(self, us, targets) -> np.ndarray:
        """Multi-source MR: per target, the best MR from any source
        (``targets`` keeps caller order and duplicates)."""
        self._require_workload("mr_set")
        from repro.workloads.setops import cross_pairs, normalize_vertex_set
        sources = normalize_vertex_set(us, self.h.n, "mr_from_set sources")
        tgt, _ = validate_batch(targets, targets, self.h.n)
        qu, qv = cross_pairs(sources, tgt)
        flat = np.asarray(self.mr_batch(qu, qv), np.int64)
        return flat.reshape(len(sources), len(tgt)).max(axis=0)

    def top_s(self, u: int, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """Top-k strongest-s ranking: the (up to) k vertices with the
        largest MR(u, .), from one full label-row sweep.  Returns
        (vertices, mr values) ranked (MR desc, id asc); zeros and ``u``
        itself never appear."""
        self._require_workload("top_s")
        from repro.workloads.topk import select_top_s
        self._check_vertex_ids(u)
        if int(k) < 1:
            raise ValueError(f"top_s needs k >= 1; got {k}")
        n = self.h.n
        row = self.mr_batch(np.full(n, int(u), np.int64),
                            np.arange(n, dtype=np.int64))
        return select_top_s(np.asarray(row), int(u), int(k))

    def s_distance(self, u: int, v: int, s: int) -> int:
        """Certified upper bound on the s-distance in hyperedges
        (0 = provably no s-walk), served off the cached landmark
        oracle for this ``s``."""
        self._require_workload("s_distance")
        self._check_vertex_ids(u, v)
        return int(self.distance_oracle(int(s)).distance(int(u), int(v)))

    def distance_oracle(self, s: int, *, extra_landmarks: int = 4,
                        ) -> "DistanceOracle":
        """The per-``s`` landmark oracle (built on first use, cached
        until the graph changes)."""
        self._require_workload("s_distance")
        if int(s) < 1:
            raise ValueError(f"s-distance needs s >= 1; got {s}")
        key = (int(s), int(extra_landmarks))
        oracle = self._distance_oracles.get(key)
        if oracle is None:
            from repro.workloads.oracle import DistanceOracle
            oracle = DistanceOracle(self.h, int(s),
                                    extra_landmarks=int(extra_landmarks))
            self._distance_oracles[key] = oracle
        return oracle

    def block_until_built(self) -> None:
        """Block until any device work dispatched by ``build`` is resident
        (jax dispatch is asynchronous).  Backends whose build is host-side
        (or already synchronous) inherit this no-op; async-building
        backends (e.g. ``sharded``) override it so build timing and
        serving hand-off are well-defined."""

    def nbytes(self) -> Optional[int]:
        """Resident index size in bytes, if the backend tracks one."""
        return None


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Callable] = {}


def register_backend(name: str, builder: Optional[Callable] = None):
    """Register ``builder`` (a class with ``.build(h, **opts)``) under
    ``name``.  Usable as a decorator: ``@register_backend("hl-index")``."""
    def deco(cls):
        _REGISTRY[name] = cls
        return cls
    if builder is not None:
        return deco(builder)
    return deco


def available_backends() -> List[str]:
    """Sorted registry keys (excludes the virtual ``"auto"``)."""
    return sorted(_REGISTRY)


def update_capabilities() -> Dict[str, str]:
    """Registry key -> declared ``update(inserts, deletes)`` capability
    ("scoped" | "incremental" | "rebuild" | "unsupported").  The table in
    docs/ARCHITECTURE.md is CI-checked against this (tools/check_docs.py).
    """
    return {name: getattr(cls, "update_capability", "unsupported")
            for name, cls in sorted(_REGISTRY.items())}


def workload_capabilities() -> Dict[str, Dict[str, bool]]:
    """Registry key -> {workload op -> served?} in ``WORKLOAD_OPS``
    order.  The workload-capability table in docs/ARCHITECTURE.md is
    CI-checked against this both ways (tools/check_docs.py check 9),
    and the conformance matrix derives its supported/unsupported cells
    from it."""
    caps: Dict[str, Dict[str, bool]] = {}
    for name, cls in sorted(_REGISTRY.items()):
        served = getattr(cls, "workload_capability", frozenset())
        caps[name] = {op: op in served for op in WORKLOAD_OPS}
    return caps


def plan_backend(h: Hypergraph, batch_hint: Optional[int] = None, *,
                 mesh=None, device_budget_bytes: Optional[int] = None,
                 join_batch: Optional[int] = None) -> str:
    """Pick a backend from graph size, label mass, query batch shape, and
    (optionally) the device topology and memory.

    Args:
      h: the hypergraph to serve.
      batch_hint: expected query batch size (None/0 = trickle queries).
      mesh: an optional ``jax.sharding.Mesh``.  A mesh with more than one
        device opts the workload into distribution: if the dense closure
        working set (~12·m² bytes: operand + two gathered f32 panels)
        exceeds ``device_budget_bytes``, the planner picks ``sharded``.
        A unit mesh (1 device) never routes to ``sharded``.
      device_budget_bytes: per-device memory budget.  The ``sharded``
        rule defaults it to ``SINGLE_DEVICE_CLOSURE_BUDGET``; the
        single-device ``closure`` rule to ``device_memory_budget()``
        (three quarters of the device's reported memory).
      join_batch: the largest query batch one join runs (the service
        passes its ``max_batch``); defaults to ``batch_hint`` or
        ``CLOSURE_JOIN_BATCH``, whichever is larger.

    Policy (documented in README.md and docs/ARCHITECTURE.md):
      * multi-device mesh + closure beyond one device -> ``sharded``
        (2-D block-sharded semiring closure, mesh-sharded snapshot);
      * tiny line graphs with real batches -> dense semiring ``closure``
        (one fused device program, no per-root host traversal);
      * anything where HL-index construction is tractable -> ``hl-index``
        (the paper's answer: microsecond merge-joins, batch via
        snapshot).  On a multi-device mesh the tractability ceiling
        scales with the parallelism actually deliverable — the device
        count capped by the host's cores: construction itself shards
        across the mesh (``build_engine`` forwards the mesh, so
        ``HLIndexEngine.build`` picks ``construction="sharded"`` and
        ``build_sharded`` defaults a matching worker pool — see
        ``repro.core.hlindex``), so larger graphs still label-build
        instead of falling back to traversal backends.  Known limit of
        the heuristic: shards stop at line-graph component boundaries,
        so a single-component graph cannot actually parallelize — the
        planner cannot see that without computing the neighbor index it
        exists to avoid, so the scaled budget is optimistic there
        (sub-component root-range sharding is the ROADMAP item that
        closes this);
      * label build over budget, but the closure regime's device working
        set (``closure_working_set_bytes``: overlap matrix, vertex rows,
        one ``join_batch`` join) fits the device and its rows build
        (``closure_build_pairs``) fits ``CLOSURE_BUILD_BUDGET`` ->
        ``closure`` (dense, high-overlap graphs with n ≪ m: the rows
        build on the device; an n ≈ m graph fails the build budget);
      * huge graphs, batched workload -> ``frontier`` (index-free sparse
        sweeps; build cost is one line-graph pass);
      * huge graphs, trickle queries -> ``online`` (no build at all).
    """
    q = int(batch_hint) if batch_hint else 0
    if h.m == 0:
        return "hl-index"
    devices = int(mesh.devices.size) if mesh is not None else 1
    if devices > 1 and len(mesh.axis_names) >= 2:
        # sharded needs two mesh axes to 2-D block-shard over; a 1-D mesh
        # falls through to the single-device policy rather than routing
        # to a backend that cannot be built on it
        budget = (SINGLE_DEVICE_CLOSURE_BUDGET if device_budget_bytes is None
                  else int(device_budget_bytes))
        if 12 * h.m * h.m > budget:
            return "sharded"
    if h.m <= 256 and q >= 64:
        return "closure"
    # label mass proxy: construction walks ~nnz * avg-degree host work;
    # sharded construction divides it across forked workers, so the
    # budget scales with the parallelism actually deliverable — the
    # mesh device count capped by the host's cores (build_engine
    # forwards the mesh, and build_sharded defaults its worker pool to
    # exactly this on a multi-device mesh)
    parallel = min(devices, os.cpu_count() or 1) if devices > 1 else 1
    label_budget = 2e6 * max(parallel, 1)
    if h.nnz * max(float(h.vertex_degrees.mean()) if h.n else 0.0, 1.0) \
            <= label_budget:
        return "hl-index"
    join = int(join_batch) if join_batch else max(q, CLOSURE_JOIN_BATCH)
    budget = (device_memory_budget() if device_budget_bytes is None
              else int(device_budget_bytes))
    if (closure_working_set_bytes(h.n, h.m, join) <= budget
            and closure_build_pairs(h.n, h.m) <= CLOSURE_BUILD_BUDGET):
        return "closure"
    if q >= 256:
        return "frontier"
    return "online"


def build(h: Optional[Hypergraph] = None, backend: str = "auto", *,
          restore=None, batch_hint: Optional[int] = None, mesh=None,
          **opts) -> "ReachabilityEngine":
    """Build a reachability engine over ``h`` — or restore one from disk.

    Args:
      h: the hypergraph to serve (omit iff ``restore`` is given).
      backend: a registry key (see ``available_backends()``) or
        ``"auto"`` to let ``plan_backend`` choose.  With ``restore`` a
        non-auto value asserts what the persisted engine must be.
      restore: path to a ``repro.store`` artifact — an ``IndexStore``
        directory (checkpoint + WAL replay + re-attach, the warm-restart
        path) or a single ``save_index`` file.  No construction runs:
        the index loads mmap-backed and only the journaled update suffix
        replays.
      batch_hint: expected query batch size, consumed by the planner.
      mesh: optional ``jax.sharding.Mesh``.  Consulted by the planner
        (see ``plan_backend``) and forwarded to the ``sharded`` backend;
        ignored by single-device backends.  A restored ``sharded``
        engine re-shards onto it.
      **opts: backend-specific options, passed to the backend's
        ``build`` (e.g. ``minimize_labels=False`` or
        ``construction="sharded"`` for "hl-index", ``schedule="ring"``
        or ``build_labels=True`` for "sharded", ``device_budget_bytes``
        for the planner) — or, with ``restore``, the
        ``restore_engine`` options (``verify``, ``checkpoint_every``,
        ``attach``).
    """
    if restore is not None:
        if h is not None:
            raise ValueError(
                "build(restore=...) loads a persisted engine; passing a "
                "hypergraph too is ambiguous — use one or the other")
        from ..store import restore_engine
        return restore_engine(
            restore, mesh=mesh,
            expect_backend=None if backend == "auto" else backend, **opts)
    if h is None:
        raise ValueError("build() needs a hypergraph (or restore=<path>)")
    budget = opts.pop("device_budget_bytes", None)
    if backend == "auto":
        backend = plan_backend(h, batch_hint, mesh=mesh,
                               device_budget_bytes=budget)
    try:
        cls = _REGISTRY[backend]
    except KeyError:
        raise ValueError(
            f"unknown backend {backend!r}; available: {available_backends()}"
        ) from None
    if mesh is not None and backend in _MESH_AWARE_BACKENDS:
        opts.setdefault("mesh", mesh)
    return cls.build(h, **opts)


# Backends whose ``build`` consumes a device mesh: "sharded" block-shards
# its closure over it; the HL-index backends shard *construction* over it
# (neighbor overlaps on device, per-device component shards).
_MESH_AWARE_BACKENDS = frozenset({"sharded", "hl-index", "hl-index-basic"})


# ---------------------------------------------------------------------------
# HL-index backends (the paper's structure)
# ---------------------------------------------------------------------------

def _resolve_construction(construction: str, mesh, workers,
                          num_shards) -> str:
    """The one auto-resolution rule both HL-index backends share:
    ``"auto"`` means sharded construction iff a multi-device mesh,
    ``workers``, or ``num_shards`` asks for it; anything else must be a
    ``CONSTRUCTION_MODES`` key."""
    if construction == "auto":
        return ("sharded"
                if (workers or num_shards
                    or (mesh is not None and int(mesh.devices.size) > 1))
                else "serial")
    if construction not in CONSTRUCTION_MODES:
        raise ValueError(
            f"unknown construction {construction!r}; available: "
            f"{sorted(CONSTRUCTION_MODES)}")
    return construction

@register_backend("hl-index")
class HLIndexEngine(_EngineBase):
    """Algorithm 3 (+ Algorithm 4 minimization) served by Algorithm 5
    merge-joins; batches run on the padded device snapshot.  Updates are
    component-scoped: construction reruns only on the affected line-graph
    component(s) and is spliced into the surviving labels
    (``repro.core.maintenance``)."""

    name = "hl-index"
    update_capability = "scoped"
    workload_capability = _LABEL_OPS | _TRAVERSAL_OPS
    _gate_hop_bounded = True

    def __init__(self, h: Hypergraph, idx: HLIndex,
                 builder: Callable[[Hypergraph], HLIndex] = build_fast,
                 minimizer: Optional[Callable[[HLIndex], HLIndex]] = None):
        super().__init__(h)
        self.idx = idx
        self.construction = "serial"     # overwritten by ``build``
        self._builder = builder          # scoped-update (re)construction
        self._minimizer = minimizer      # applied to the sub-index too
        self._snap: Optional[DeviceSnapshot] = None

    @classmethod
    def build(cls, h: Hypergraph, *, minimize_labels: bool = True,
              index: Optional[HLIndex] = None,
              construction: str = "auto", mesh=None,
              workers: Optional[int] = None,
              num_shards: Optional[int] = None,
              use_kernels: bool = False) -> "HLIndexEngine":
        """``index`` reuses a prebuilt (unminimized) HL-index instead of
        running construction again — e.g. to derive the minimized engine
        from an ablation engine's labels.

        ``construction`` picks the builder from ``CONSTRUCTION_MODES``:
        ``"serial"`` (Algorithm 3 on one host thread), ``"sharded"``
        (component-sharded parallel construction — byte-identical labels,
        see ``repro.core.hlindex.build_sharded``), or ``"auto"``
        (sharded iff a multi-device ``mesh``, ``workers``, or
        ``num_shards`` asks for it).  ``mesh`` additionally routes the
        neighbor-overlap precompute onto the devices.  Scoped updates
        keep using the same construction mode on the affected
        component(s).

        ``use_kernels`` answers batch queries through the Pallas
        label-join kernel (``KernelSnapshot``) instead of the XLA
        ``batched_mr`` program — compiled on TPU, interpreted on the CPU
        backend; answers are byte-identical either way
        (conformance-matrix rows pin both).
        """
        construction = _resolve_construction(construction, mesh, workers,
                                             num_shards)
        minimizer = minimize if minimize_labels else None
        stages = Stages("repro")
        with stages.span("build", backend=cls.name,
                         construction=construction):
            if construction == "sharded":
                builder = functools.partial(build_sharded, workers=workers,
                                            num_shards=num_shards)
                if index is not None:
                    with stages.span("build.minimize"):
                        idx = minimizer(index) if minimizer else index
                else:
                    # minimization runs inside the shards too (exact:
                    # dual sets are component-confined), so the whole
                    # build parallelizes — byte-identical to
                    # minimize(build_fast(h))
                    with stages.span("build.labels"):
                        idx = build_sharded(h, minimizer=minimizer,
                                            workers=workers,
                                            num_shards=num_shards, mesh=mesh)
            else:
                builder = build_fast
                if index is None:
                    with stages.span("build.labels"):
                        index = build_fast(h, stages=stages)
                idx = index
                if minimizer is not None:
                    with stages.span("build.minimize"):
                        idx = minimizer(idx)
        eng = cls(h, idx, builder=builder, minimizer=minimizer)
        eng.build_stages = stages
        eng.construction = construction
        eng.use_kernels = bool(use_kernels)
        return eng

    def mr(self, u: int, v: int) -> int:
        self._check_vertex_ids(u, v)
        return mr_query(self.idx, int(u), int(v))

    def s_reach(self, u: int, v: int, s: int) -> bool:
        self._check_vertex_ids(u, v)
        return s_reach_query(self.idx, int(u), int(v), int(s))

    def _witness_hub(self, u: int, v: int, k: int) -> Optional[int]:
        """The Algorithm-5 join's meeting hub: a hyperedge labeled on
        both sides with min(s_u, s_v) = k (no label pair can exceed
        MR, so >= k is the argmax)."""
        label_v = self.idx.label_dict(v)
        for e, su in zip(self.idx.labels_edge[u], self.idx.labels_s[u]):
            sv = label_v.get(int(e))
            if sv is not None and min(int(su), sv) >= k:
                return int(e)
        return None

    def mr_batch(self, us, vs) -> np.ndarray:
        us, vs = validate_batch(us, vs, self.h.n)
        return np.asarray(self._query_snapshot().mr(us, vs))

    def s_reach_batch(self, us, vs, s: int) -> np.ndarray:
        us, vs = validate_batch(us, vs, self.h.n)
        return np.asarray(self._query_snapshot().s_reach(us, vs, int(s)))

    def snapshot(self) -> DeviceSnapshot:
        """Current padded device form.  After a scoped ``update`` the
        stale snapshot is patched: only the rows the ``UpdateReport``
        marked dirty are re-padded and scattered over the old tensors
        (byte-identical to a from-scratch derivation, asserted in
        tests/test_serving.py); a full-rebuild update re-derives whole.
        """
        if self._snapshot_current():
            return self._snap
        basis, dirty = self._snap, self._dirty_rows
        if basis is None or dirty is None:
            snap = DeviceSnapshot.from_hlindex(self.idx, self.name,
                                               version=self.version)
            self.last_snapshot_refresh_rows = self.h.n
        else:
            snap = self._patched_snapshot(basis, dirty)
            self.last_snapshot_refresh_rows = int(dirty.size)
        self._snap = snap
        self._dirty_rows = np.empty(0, np.int64)
        return snap

    def _patched_snapshot(self, basis: DeviceSnapshot,
                          dirty: np.ndarray) -> DeviceSnapshot:
        idx, n = self.idx, self.h.n
        lengths = np.zeros(n, np.int64)
        basis_n = int(basis.ranks.shape[0])
        lengths[:basis_n] = np.asarray(basis.lengths)
        lengths[dirty] = [idx.labels_s[int(u)].size for u in dirty]
        lmax = int(lengths.max()) if n else 0
        row_ranks, row_svals, row_lengths = pad_label_rows(
            [idx.labels_rank[int(u)] for u in dirty],
            [idx.labels_s[int(u)] for u in dirty], pad_to=lmax)
        return basis.patch_rows(dirty, row_ranks, row_svals, row_lengths,
                                n=n, lmax=lmax, version=self.version,
                                backend=self.name)

    def _apply_update(self, inserts=(), deletes=()) -> None:
        new_h, self.idx, report = apply_updates(
            self.h, self.idx, inserts, deletes,
            builder=self._builder, minimizer=self._minimizer)
        self._graph_changed(
            new_h, dirty_rows=(None if report.full_rebuild
                               else report.refreshed_vertices))

    def nbytes(self) -> int:
        return self.idx.nbytes()


@register_backend("hl-index-basic")
class HLIndexBasicEngine(HLIndexEngine):
    """Algorithm 2 construction (no MCD/neighbor-index pruning, no
    minimization) — the ablation baseline, same query and scoped-update
    paths (updates rebuild the affected components with Algorithm 2)."""

    name = "hl-index-basic"

    @classmethod
    def build(cls, h: Hypergraph, *, cover_check: bool = True,
              construction: str = "auto", mesh=None,
              workers: Optional[int] = None,
              num_shards: Optional[int] = None,
              use_kernels: bool = False) -> "HLIndexBasicEngine":
        base = functools.partial(build_basic, cover_check=cover_check)
        construction = _resolve_construction(construction, mesh, workers,
                                             num_shards)
        if construction == "sharded":
            builder = functools.partial(build_sharded, base=base,
                                        workers=workers,
                                        num_shards=num_shards)
            idx = build_sharded(h, base=base, workers=workers,
                                num_shards=num_shards, mesh=mesh)
        else:
            builder = base
            idx = base(h)
        eng = cls(h, idx, builder=builder)
        eng.construction = construction
        eng.use_kernels = bool(use_kernels)
        return eng


# ---------------------------------------------------------------------------
# Index-free backends
# ---------------------------------------------------------------------------

@register_backend("online")
class OnlineEngine(_EngineBase):
    """Algorithm 1 bidirectional search (the paper's Base*); zero build
    cost beyond the optional neighbor cache, which updates patch on the
    1-hop touched set only."""

    name = "online"
    update_capability = "incremental"
    workload_capability = _TRAVERSAL_OPS

    def __init__(self, h: Hypergraph, cache: Optional[NeighborCache]):
        super().__init__(h)
        self.cache = cache

    @classmethod
    def build(cls, h: Hypergraph, *, precompute: bool = True) -> "OnlineEngine":
        return cls(h, NeighborCache(h) if precompute else None)

    def mr(self, u: int, v: int) -> int:
        self._check_vertex_ids(u, v)
        return mr_online(self.h, int(u), int(v), self.cache)

    def _apply_update(self, inserts=(), deletes=()) -> None:
        new_h, old_to_new, touched = apply_edge_edits(self.h, inserts,
                                                      deletes)
        if self.cache is not None:
            self.cache = self.cache.updated(new_h, old_to_new, touched)
        self._graph_changed(new_h)

    def nbytes(self) -> Optional[int]:
        return self.cache.nbytes() if self.cache is not None else 0


@register_backend("frontier")
class FrontierEngine(_EngineBase):
    """Index-free sparse line-graph frontier sweeps — the batch path for
    graphs beyond dense-closure scale.  ``rounds`` bounds propagation
    (None = |E|, exact)."""

    name = "frontier"
    update_capability = "incremental"
    workload_capability = _TRAVERSAL_OPS

    def __init__(self, h: Hypergraph, g: SparseLineGraph,
                 rounds: Optional[int]):
        super().__init__(h)
        self.g = g
        self.rounds = rounds

    @classmethod
    def build(cls, h: Hypergraph, *,
              rounds: Optional[int] = None) -> "FrontierEngine":
        return cls(h, SparseLineGraph(h), rounds)

    def _apply_update(self, inserts=(), deletes=()) -> None:
        new_h, old_to_new, touched = apply_edge_edits(self.h, inserts,
                                                      deletes)
        self.g = self.g.updated(new_h, old_to_new, touched)
        self._graph_changed(new_h)

    def mr(self, u: int, v: int) -> int:
        return int(self.mr_batch([int(u)], [int(v)])[0])

    def s_reach(self, u: int, v: int, s: int) -> bool:
        return bool(self.s_reach_batch([int(u)], [int(v)], int(s))[0])

    def mr_batch(self, us, vs) -> np.ndarray:
        us, vs = validate_batch(us, vs, self.h.n)
        return frontier_batched_mr(self.g, us, vs, rounds=self.rounds)

    def s_reach_batch(self, us, vs, s: int) -> np.ndarray:
        us, vs = validate_batch(us, vs, self.h.n)
        return frontier_batched_s_reach(self.g, us, vs, int(s),
                                        rounds=self.rounds)

    def _bounded_s_reach(self, u: int, v: int, s: int, k: int) -> bool:
        # bounded *device* path: a walk of k hyperedges is k - 1
        # line-graph steps of the jitted frontier sweep
        return bool(frontier_batched_s_reach(
            self.g, [u], [v], s, rounds=k - 1)[0])


# ---------------------------------------------------------------------------
# Baseline backends (Section IV / VII structures)
# ---------------------------------------------------------------------------

@register_backend("ete")
class ETEEngine(_EngineBase):
    """Hyperedge-to-hyperedge 2-hop labeling; snapshot merges each
    vertex's incident label lists into the shared padded form."""

    name = "ete"
    # label-row reductions only: the structure is static (updates
    # unsupported), so the live-traversal ops stay off
    workload_capability = _LABEL_OPS

    def __init__(self, h: Hypergraph, ete: ETEIndex):
        super().__init__(h)
        self.ete = ete
        self._snap: Optional[DeviceSnapshot] = None

    @classmethod
    def build(cls, h: Hypergraph) -> "ETEEngine":
        return cls(h, build_ete(h))

    def mr(self, u: int, v: int) -> int:
        self._check_vertex_ids(u, v)
        return self.ete.mr(int(u), int(v))

    def mr_batch(self, us, vs) -> np.ndarray:
        us, vs = validate_batch(us, vs, self.h.n)
        return np.asarray(self._query_snapshot().mr(us, vs))

    def s_reach_batch(self, us, vs, s: int) -> np.ndarray:
        us, vs = validate_batch(us, vs, self.h.n)
        return np.asarray(self._query_snapshot().s_reach(us, vs, int(s)))

    def snapshot(self) -> DeviceSnapshot:
        if not self._snapshot_current():
            merged = [self.ete._merged(self.h.edges_of(u))
                      for u in range(self.h.n)]
            ranks, svals, lengths = pad_label_rows([r for r, _ in merged],
                                                   [s for _, s in merged])
            self._snap = DeviceSnapshot.from_padded(ranks, svals, lengths,
                                                    self.name,
                                                    version=self.version)
        return self._snap

    def nbytes(self) -> int:
        return self.ete.nbytes()


@register_backend("threshold")
class ThresholdEngine(_EngineBase):
    """HypED-style per-threshold union-find components (exact; storage
    O(S·m) — the blow-up the paper contrasts against)."""

    name = "threshold"

    def __init__(self, h: Hypergraph, tci: ThresholdComponentIndex):
        super().__init__(h)
        self.tci = tci

    @classmethod
    def build(cls, h: Hypergraph, *,
              cap: Optional[int] = None) -> "ThresholdEngine":
        return cls(h, ThresholdComponentIndex(h, cap=cap))

    def mr(self, u: int, v: int) -> int:
        self._check_vertex_ids(u, v)
        return self.tci.mr(int(u), int(v))

    def nbytes(self) -> int:
        return self.tci.nbytes()


@register_backend("mst-oracle")
class MSTOracleEngine(_EngineBase):
    """Maximum-spanning-forest bottleneck oracle — the independent exact
    reference the cross-validation suite pins every backend against."""

    name = "mst-oracle"

    def __init__(self, h: Hypergraph, oracle: MSTOracle):
        super().__init__(h)
        self.oracle = oracle

    @classmethod
    def build(cls, h: Hypergraph) -> "MSTOracleEngine":
        return cls(h, MSTOracle(h))

    def mr(self, u: int, v: int) -> int:
        self._check_vertex_ids(u, v)
        return self.oracle.mr(int(u), int(v))


@register_backend("closure")
class ClosureEngine(_EngineBase):
    """Per-vertex rows of the dense (max, min)-semiring closure
    (``semiring.vertex_rows``), ``R[v, e] = max_{e_v ∋ v} W*[e_v, e]``
    [n, m], built on the device; W* itself is never formed.

    Its snapshot is the degenerate-but-exact label form: every hyperedge
    is a hub, ``L(v)[e] = R[v, e]``.  Bottleneck triangle inequality
    makes the shared label join exact on these rows (equality is
    attained at the hub e = e_u of an optimal pair).  A scalar lookup
    reads one row: ``MR(u, v) = max_{e_v ∋ v} R[u, e_v]``.
    """

    name = "closure"
    update_capability = "rebuild"
    workload_capability = _LABEL_OPS | _TRAVERSAL_OPS
    _gate_hop_bounded = True

    def __init__(self, h: Hypergraph, rows, rounds: Optional[int] = None):
        super().__init__(h)
        self.rows = rows                 # [n, m] int32, device or host
        self.rounds = rounds             # propagation rounds of the build
        self._snap: Optional[DeviceSnapshot] = None

    @classmethod
    def build(cls, h: Hypergraph) -> "ClosureEngine":
        stages = Stages("repro")
        with stages.span("build", backend=cls.name):
            rows, rounds = vertex_rows(h, stages=stages)
        eng = cls(h, rows, rounds)
        eng.build_stages = stages
        return eng

    def _apply_update(self, inserts=(), deletes=()) -> None:
        # dense closures have no cheap incremental form (one new overlap
        # can rewrite O(n·m) entries); recompute whole, same protocol
        new_h, _, _ = apply_edge_edits(self.h, inserts, deletes)
        self.rows = None                 # free the old rows first
        self._graph_changed(new_h)
        self.rows, self.rounds = vertex_rows(new_h)

    def mr(self, u: int, v: int) -> int:
        # scalar lookups fetch u's row alone (no reason to build the
        # [n, m] snapshot for a trickle of queries)
        self._check_vertex_ids(u, v)
        ev = self.h.edges_of(int(v))
        return int(np.asarray(self.rows[int(u)])[ev].max()) if ev.size else 0

    def mr_batch(self, us, vs) -> np.ndarray:
        # batches go through the fused device join — the reason the
        # planner picks this backend for batched workloads
        us, vs = validate_batch(us, vs, self.h.n)
        return np.asarray(self._query_snapshot().mr(us, vs))

    def s_reach_batch(self, us, vs, s: int) -> np.ndarray:
        us, vs = validate_batch(us, vs, self.h.n)
        return np.asarray(self._query_snapshot().s_reach(us, vs, int(s)))

    def snapshot(self) -> DeviceSnapshot:
        if not self._snapshot_current():
            import jax.numpy as jnp
            n, m = self.h.n, self.h.m
            ranks = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32), (n, m))
            self._snap = DeviceSnapshot.from_padded(
                ranks, self.rows, np.full(n, m, np.int32), self.name,
                version=self.version)
            self.last_snapshot_refresh_rows = n
            self._dirty_rows = np.empty(0, np.int64)
        return self._snap

    def nbytes(self) -> int:
        return int(self.rows.nbytes)


# ---------------------------------------------------------------------------
# Multi-device backend — lives in distributed.py; importing it here
# registers "sharded" so the registry is complete after `import engine`.
# ---------------------------------------------------------------------------

from . import distributed as _distributed  # noqa: E402,F401
