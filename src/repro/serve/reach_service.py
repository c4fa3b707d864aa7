"""Request-based reachability serving: ``ReachabilityService``.

The engine API (``repro.core.engine``) is imperative — callers invoke
``eng.mr_batch`` with batches they assembled themselves, and after an
``update`` they must notice staleness and re-derive snapshots by hand.
This module turns that surface into a *service*: callers submit typed
requests and get futures; an admission loop coalesces whatever is
pending into fused padded device batches and scatters the answers back.

    svc = repro.api.serve(h, config=ServiceConfig(max_batch=1024))
    f1 = svc.mr(4, 8)                           # Future[int]
    f2 = svc.submit(SReachRequest(4, 8, s=2))   # Future[bool]
    f1.result(), f2.result()
    svc.update(inserts=[[3, 7, 9]])             # serving continues
    svc.close()

Design (the mechanisms the module exists for):

* **Admission micro-batching** — pending requests are grouped by query
  kind (``MRRequest`` vs ``SReachRequest``) and each group is padded to
  a power-of-two bucket size (``min_bucket`` .. ``max_batch``) before
  dispatch.  The fused ``batched_mr`` join recompiles per batch *shape*,
  so bucketing bounds the number of distinct XLA programs to
  ``log2(max_batch / min_bucket) + 1`` per kind instead of one per
  distinct queue depth.  Padding slots repeat a real query pair, which
  is semantically inert (answers past the true count are dropped before
  scatter).  Mixed ``s`` values coalesce into one fused batch: on the
  snapshot path every s-reach answer is ``mr >= s`` off the same join.
* **Multi-tenant admission** — every request carries ``tenant`` /
  ``priority`` / ``deadline_ms`` metadata (defaults reproduce the old
  single-tenant behavior exactly).  The queue is a
  ``WeightedFairScheduler``: strict priority bands, deficit-weighted
  round-robin across tenants within a band, deadline-expired requests
  failed fast with ``DeadlineExceeded``.  A flooding tenant shapes only
  its own share of each micro-batch, never anyone else's wait.
* **Streaming delivery** — ``submit_stream()`` yields ``(request,
  future)`` pairs in *completion* order as micro-batches resolve them,
  and ``submit(..., on_result=fn)`` invokes a callback the moment one
  request's answer lands — both are thin layers over the same futures.
* **Version-keyed snapshot reuse** — the service serves every batch off
  one resident ``DeviceSnapshot`` keyed by ``engine.version``.  After
  ``update()`` the swap happens *between* micro-batches (never mid
  batch): the admission loop notices ``snap.version != engine.version``
  and asks the engine for ``snapshot_delta()`` — a fresh snapshot plus
  the dirty-row delta scoped maintenance reported — and installs it
  with a single atomic reference swap.
* **Mesh-resident serving** — pass ``mesh=`` and the resident snapshot
  lives sharded over the device mesh (``DeviceSnapshot.to_mesh``).
  After a scoped update, only the dirty rows are re-landed into the
  mesh-resident copy (``to_mesh(base=..., dirty_rows=...)``) instead of
  re-transferring the whole label mass.  ``repro.serve.replicas``
  builds read-replica fan-out on the same contract.

Backends with no snapshot form (``online``, ``frontier``, ...) are
served through their own ``mr_batch`` / ``s_reach_batch`` engines by the
same admission loop — the service degrades, never refuses.

Workload request kinds (``witness`` / ``s_reach_k`` / ``mr_set`` /
``top_s`` / ``s_distance``, see ``repro.workloads``) ride the same
admission pipeline: typed frozen requests, the same tenant/priority/
deadline metadata, and their own per-kind dispatch groups — so workload
traffic never perturbs the padded mr/s_reach bucket shapes.  Kinds a
backend cannot serve are refused at *admission* with
``WorkloadUnsupported`` (checked against ``engine.workload_capability``)
rather than failing futures later.

The request-type, priority-class, and request-field tables in
docs/ARCHITECTURE.md are CI-checked against ``REQUEST_TYPES``,
``PRIORITY_CLASSES``, and the ``Request`` base dataclass
(tools/check_docs.py).
"""
from __future__ import annotations

import dataclasses
import operator
import queue as queue_mod
import threading
import time
from concurrent.futures import Future, InvalidStateError
from typing import (Callable, Dict, Iterable, Iterator, List, Optional,
                    Sequence, Tuple)

import numpy as np

from repro.core.engine import SnapshotUnsupported, WorkloadUnsupported
from repro.core.query import KernelSnapshot
from repro.serve.scheduler import (PRIORITY_CLASSES, DeadlineExceeded,
                                   TenantSpec, WeightedFairScheduler, _Entry)
from repro.stages import Stages, StageTotal

__all__ = ["Request", "MRRequest", "SReachRequest", "WitnessRequest",
           "SReachKRequest", "MRSetRequest", "TopSRequest",
           "SDistanceRequest", "ReachabilityService",
           "ServiceConfig", "ServiceStats", "REQUEST_TYPES",
           "PRIORITY_CLASSES", "TenantSpec", "DeadlineExceeded"]


@dataclasses.dataclass(frozen=True)
class Request:
    """Frozen base every service request derives from.  Carries the
    multi-tenant scheduling metadata; all three fields are keyword-only
    with defaults that reproduce the pre-multi-tenant behavior exactly
    (one implicit tenant, one band, no deadline) — ``MRRequest(4, 8)``
    means what it always meant.

    The field table in docs/ARCHITECTURE.md documents exactly these
    fields and CI fails if they drift (tools/check_docs.py check 8).
    """

    tenant: str = dataclasses.field(default="default", kw_only=True)
    priority: str = dataclasses.field(default="standard", kw_only=True)
    deadline_ms: Optional[float] = dataclasses.field(default=None,
                                                     kw_only=True)


@dataclasses.dataclass(frozen=True)
class MRRequest(Request):
    """Problem 2: answer ``MR(u, v)`` — resolves to ``int``."""

    u: int
    v: int

    kind = "mr"


@dataclasses.dataclass(frozen=True)
class SReachRequest(Request):
    """Problem 1: is there an s-walk joining ``u`` and ``v`` — resolves
    to ``bool``.  Requests with different ``s`` coalesce into the same
    fused batch (the snapshot path answers all of them off one join)."""

    u: int
    v: int
    s: int

    kind = "s_reach"


@dataclasses.dataclass(frozen=True)
class WitnessRequest(Request):
    """Workload: MR with proof — resolves to a ``repro.workloads.Witness``
    whose hyperedge walk realizes ``MR(u, v)`` (empty walk when 0)."""

    u: int
    v: int

    kind = "witness"


@dataclasses.dataclass(frozen=True)
class SReachKRequest(Request):
    """Workload: hop-bounded s-reach — is there an s-walk of at most
    ``k`` hyperedges joining ``u`` and ``v``; resolves to ``bool``."""

    u: int
    v: int
    s: int
    k: int

    kind = "s_reach_k"


@dataclasses.dataclass(frozen=True)
class MRSetRequest(Request):
    """Workload: set-to-set MR — ``max`` of ``MR(u, v)`` over
    ``us x vs``; resolves to ``int``.  Vertex sets are stored as tuples
    so the request stays frozen/hashable."""

    us: Tuple[int, ...]
    vs: Tuple[int, ...]

    kind = "mr_set"

    def __post_init__(self):
        object.__setattr__(self, "us", tuple(self.us))
        object.__setattr__(self, "vs", tuple(self.vs))


@dataclasses.dataclass(frozen=True)
class TopSRequest(Request):
    """Workload: top-k strongest-s ranking — resolves to a tuple of
    ``(vertex, mr)`` pairs sorted by descending ``mr`` (ties by vertex
    id), zeros and ``u`` itself excluded."""

    u: int
    k: int

    kind = "top_s"


@dataclasses.dataclass(frozen=True)
class SDistanceRequest(Request):
    """Workload: landmark s-distance — resolves to an ``int`` certified
    upper bound on the number of hyperedges an s-walk from ``u`` to
    ``v`` needs (0 = provably no s-walk)."""

    u: int
    v: int
    s: int

    kind = "s_distance"


# kind -> request class; the serving section of docs/ARCHITECTURE.md
# documents exactly this table and CI fails if they drift apart
REQUEST_TYPES: Dict[str, type] = {MRRequest.kind: MRRequest,
                                  SReachRequest.kind: SReachRequest,
                                  WitnessRequest.kind: WitnessRequest,
                                  SReachKRequest.kind: SReachKRequest,
                                  MRSetRequest.kind: MRSetRequest,
                                  TopSRequest.kind: TopSRequest,
                                  SDistanceRequest.kind: SDistanceRequest}

# workload kinds gate on engine.workload_capability at submit; "mr" and
# "s_reach" (the padded-bucket kinds) every backend serves
_KIND_TO_OP: Dict[str, str] = {"witness": "witness",
                               "s_reach_k": "s_reach_k",
                               "mr_set": "mr_set",
                               "top_s": "top_s",
                               "s_distance": "s_distance"}


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Typed service configuration — the one documented way to set
    serving knobs (``repro.api.serve(h, config=ServiceConfig(...))``).

    Batching: ``max_batch`` (admission cap / largest bucket),
    ``min_bucket`` (smallest padded shape), ``max_wait_ms`` (coalescing
    linger; 0 dispatches immediately).

    Placement: ``axes`` (mesh (row, column) axis names for ``to_mesh``),
    ``use_kernels`` (serve snapshot batches through the Pallas
    label-join ``KernelSnapshot``; ``None`` inherits the engine flag).

    Scheduling: ``tenants`` (``TenantSpec`` shares; unlisted tenants get
    ``default_weight``), ``quantum`` (DRR credits per pass — larger
    means coarser interleaving within a batch, same long-run shares).

    Fan-out: ``replicas`` — when > 1, ``repro.api.serve`` builds a
    ``ReplicaGroup`` of that many mesh-resident snapshot replicas
    instead of a single-snapshot service.
    """

    max_batch: int = 4096
    min_bucket: int = 8
    max_wait_ms: float = 0.5
    axes: Optional[Tuple[str, str]] = None
    use_kernels: Optional[bool] = None
    tenants: Tuple[TenantSpec, ...] = ()
    default_weight: float = 1.0
    quantum: int = 8
    replicas: int = 1

    def __post_init__(self):
        object.__setattr__(self, "max_batch", int(self.max_batch))
        object.__setattr__(self, "min_bucket", int(self.min_bucket))
        object.__setattr__(self, "max_wait_ms", float(self.max_wait_ms))
        object.__setattr__(self, "tenants", tuple(self.tenants))
        object.__setattr__(self, "quantum", int(self.quantum))
        object.__setattr__(self, "replicas", int(self.replicas))
        if (self.max_batch < 1 or self.min_bucket < 1
                or self.min_bucket > self.max_batch):
            raise ValueError(
                f"need 1 <= min_bucket <= max_batch; got min_bucket="
                f"{self.min_bucket} max_batch={self.max_batch}")
        for spec in self.tenants:
            if not isinstance(spec, TenantSpec):
                raise TypeError(
                    f"ServiceConfig.tenants entries must be TenantSpec; "
                    f"got {spec!r}")
        if not float(self.default_weight) > 0:
            raise ValueError(
                f"default_weight must be > 0; got {self.default_weight!r}")
        if self.quantum < 1:
            raise ValueError(f"quantum must be >= 1; got {self.quantum}")
        if self.replicas < 1:
            raise ValueError(f"replicas must be >= 1; got {self.replicas}")


@dataclasses.dataclass
class ServiceStats:
    """Counters the admission loop maintains (read via ``stats()``).

    ``stages`` holds the totals of the service's stage spans
    (``repro.serve.<stage>``, see ``ReachabilityService``) as
    ``repro.stages.StageTotal``: per stage, runs, wall seconds and the
    running thread's CPU share.  ``queue_wait_s / queued`` is the mean
    time a request waited in the queue before a take selected it."""

    submitted: int = 0
    answered: int = 0
    expired: int = 0                 # failed fast with DeadlineExceeded
    batches: int = 0
    padded_queries: int = 0          # bucket padding slots dispatched
    bucket_histogram: Dict[int, int] = dataclasses.field(default_factory=dict)
    tenant_submitted: Dict[str, int] = dataclasses.field(default_factory=dict)
    tenant_answered: Dict[str, int] = dataclasses.field(default_factory=dict)
    tenant_expired: Dict[str, int] = dataclasses.field(default_factory=dict)
    snapshot_refreshes: int = 0
    rows_rederived: int = 0          # label rows re-derived across refreshes
    rows_full: int = 0               # rows a from-scratch refresh would cost
    mesh_rows_patched: int = 0       # rows re-landed into a mesh-resident copy
    kernel_batches: int = 0          # batches answered by the Pallas join
    join_form_groups: Dict[str, int] = dataclasses.field(
        default_factory=dict)        # groups by join form (_join_form)
    workload_answered: Dict[str, int] = dataclasses.field(
        default_factory=dict)        # per-kind workload answers served
    updates: int = 0
    queued: int = 0                  # requests taken off the queue
    queue_wait_s: float = 0.0        # their summed time in the queue
    stages: Dict[str, StageTotal] = dataclasses.field(default_factory=dict)

    def as_dict(self) -> Dict[str, object]:
        d = dataclasses.asdict(self)
        for key in ("bucket_histogram", "tenant_submitted",
                    "tenant_answered", "tenant_expired",
                    "workload_answered", "join_form_groups", "stages"):
            d[key] = dict(sorted(d[key].items()))
        return d


def _resolve(fut: Future, value) -> None:
    """Resolve one future, tolerating a caller's concurrent ``cancel()``
    (a bare ``cancelled()`` pre-check races: the cancel can land between
    the check and ``set_result``, and the resulting InvalidStateError
    would poison the whole micro-batch through the dispatch error
    handler)."""
    try:
        fut.set_result(value)
    except InvalidStateError:
        pass                         # cancelled mid-dispatch: drop quietly


def _join_form(snap) -> str:
    """The join a group is answered by: the snapshot's own form
    (``DeviceSnapshot.join_form``: ``"aligned"`` or ``"merge"``),
    ``"kernel"`` through the Pallas view, ``"engine"`` with no snapshot."""
    if snap is None:
        return "engine"
    if isinstance(snap, KernelSnapshot):
        return "kernel"
    return snap.join_form


def _bucket_size(q: int, min_bucket: int, max_batch: int) -> int:
    """Smallest power-of-two >= q, clamped to [min_bucket, max_batch]."""
    b = 1 << max(q - 1, 0).bit_length()
    return max(min(max(b, min_bucket), max_batch), q)


class ReachabilityService:
    """Request-based serving over any ``ReachabilityEngine``.

    Args:
      engine: a built engine (``repro.api.build_engine``) — the service
        owns its snapshot lifecycle from here on.
      config: a ``ServiceConfig``; the typed home of every serving knob
        (batching, scheduling, placement).  Defaults to
        ``ServiceConfig()``.
      mesh: optional ``jax.sharding.Mesh``; the resident snapshot is
        kept mesh-sharded (``to_mesh``) and refreshed row-wise after
        scoped updates.  Ignored for backends with no snapshot form.
      start: start the background admission thread.  With
        ``start=False`` the service is synchronous: call ``drain()`` to
        process everything pending (deterministic; what the tests and
        benchmarks use).
      axes / max_batch / min_bucket / max_wait_ms / use_kernels: direct
        overrides of the matching ``config`` field (convenience for
        call sites tuning one knob; ``None`` = take the config value).

    ``use_kernels=None`` inherits the engine's own ``use_kernels`` flag,
    so ``serve(h, backend, config=ServiceConfig(use_kernels=True))``
    flips both build and serving.  The kernel view shares this service's
    admission buckets (``min_bucket``), so traffic compiles one kernel
    program per bucket shape.

    Stage spans (``repro.stages``): the thread that dispatches (the
    admission thread, or the caller of ``drain``) times each stage into
    ``stats().stages`` and, while a profiler runs, annotates it as
    ``repro.serve.<stage>``: ``wait`` (queue empty), ``linger`` (the
    ``max_wait_ms`` coalescing wait), ``take`` (selection off the queue
    and failing expired requests; meta ``batch``, ``taken``, ``wait_s``,
    the taken requests' summed queue wait), ``dispatch`` (one take's
    micro-batch; meta ``batch``, the take's), and inside it ``refresh``
    (the snapshot swap), then per kind group ``prepare`` (ids into
    arrays, padding; meta ``kind``, ``q``, ``bucket``), ``join`` (the
    device call through the fetch of its answers; meta ``bucket``) and
    ``resolve`` (futures and their callbacks; meta ``q``).  ``update``
    is timed on the updating thread while it holds the dispatch lock.
    """

    # ReplicaGroup flips this; a plain service refuses a replicated
    # config rather than silently serving one copy
    _replica_aware = False

    def __init__(self, engine, *, config: Optional[ServiceConfig] = None,
                 mesh=None, axes: Optional[Tuple[str, str]] = None,
                 max_batch: Optional[int] = None,
                 min_bucket: Optional[int] = None,
                 max_wait_ms: Optional[float] = None,
                 use_kernels: Optional[bool] = None, start: bool = True):
        cfg = config if config is not None else ServiceConfig()
        overrides = {k: v for k, v in (("axes", axes),
                                       ("max_batch", max_batch),
                                       ("min_bucket", min_bucket),
                                       ("max_wait_ms", max_wait_ms),
                                       ("use_kernels", use_kernels))
                     if v is not None}
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        if cfg.replicas > 1 and not self._replica_aware:
            raise ValueError(
                f"ServiceConfig(replicas={cfg.replicas}) needs replica "
                f"fan-out — use repro.api.serve (which builds a "
                f"ReplicaGroup) or repro.serve.replicas.ReplicaGroup "
                f"directly")
        self.config = cfg
        self.engine = engine
        self.mesh = mesh
        self.axes = cfg.axes
        self.max_batch = cfg.max_batch
        self.min_bucket = cfg.min_bucket
        self.max_wait_s = cfg.max_wait_ms / 1e3
        self._stats = ServiceStats()
        self._stages = Stages("repro.serve")
        self._batch_seq = 0          # numbers takes; a take's dispatch shares it
        self._queue = WeightedFairScheduler(
            cfg.tenants, default_weight=cfg.default_weight,
            quantum=cfg.quantum)
        self._cv = threading.Condition()
        # serializes dispatch against update(): a micro-batch always runs
        # against one coherent (engine, snapshot) pair, and the snapshot
        # swap happens strictly between batches
        self._dispatch_lock = threading.Lock()
        self._snap = None            # resident serving snapshot (mesh or host)
        self._host_snap = None       # the engine-derived snapshot _snap mirrors
        self._snapshot_ok: Optional[bool] = None   # None = not probed yet
        self.use_kernels = (bool(getattr(engine, "use_kernels", False))
                            if cfg.use_kernels is None
                            else bool(cfg.use_kernels))
        self._kernel_snap: Optional[KernelSnapshot] = None
        self._running = False
        self._thread: Optional[threading.Thread] = None
        if start:
            self.start()

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "ReachabilityService":
        with self._cv:
            if self._running:
                return self
            self._running = True
        self._thread = threading.Thread(target=self._loop,
                                        name="reach-service", daemon=True)
        self._thread.start()
        return self

    def close(self) -> None:
        """Stop the admission thread; everything already submitted is
        resolved first — answered, or failed with ``DeadlineExceeded``
        if its deadline passed (no future is left unresolved)."""
        with self._cv:
            self._running = False
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        self.drain()                 # no-thread mode: flush synchronously

    def __enter__(self) -> "ReachabilityService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- request admission -------------------------------------------------

    def submit(self, request: Request, *,
               on_result: Optional[Callable[[Request, Future], None]] = None,
               ) -> Future:
        """Enqueue one typed request; returns a ``Future`` resolving to
        the kind's answer type (``int`` for ``MRRequest`` /
        ``MRSetRequest`` / ``SDistanceRequest``, ``bool`` for
        ``SReachRequest`` / ``SReachKRequest``, a ``Witness`` for
        ``WitnessRequest``, a ``(vertex, mr)`` tuple for
        ``TopSRequest``) — or raising ``DeadlineExceeded`` if
        ``deadline_ms`` elapses first.  Workload kinds the backend
        cannot serve are refused at admission with
        ``WorkloadUnsupported`` (see ``engine.workload_capability``).

        ``on_result`` is the callback delivery hook: called as
        ``on_result(request, future)`` the moment this request's future
        resolves (from the dispatching thread), whatever the outcome.

        Validation is the same contract as ``validate_batch`` (integer
        ids in ``[0, n)``) on a scalar fast path — admission is the
        per-request hot loop, so it avoids array round-trips."""
        if not isinstance(request, tuple(REQUEST_TYPES.values())):
            raise TypeError(
                f"expected one of {sorted(REQUEST_TYPES)} requests, got "
                f"{type(request).__name__}")
        self._validate_fields(request)
        op = _KIND_TO_OP.get(request.kind)
        if op is not None and op not in getattr(
                self.engine, "workload_capability", frozenset()):
            raise WorkloadUnsupported(
                f"backend {getattr(self.engine, 'name', '?')!r} does not "
                f"serve the {op!r} workload")
        if not isinstance(request.tenant, str) or not request.tenant:
            raise ValueError(
                f"request tenant must be a non-empty string; got "
                f"{request.tenant!r}")
        if request.priority not in PRIORITY_CLASSES:
            raise ValueError(
                f"unknown priority class {request.priority!r}; available: "
                f"{sorted(PRIORITY_CLASSES)}")
        deadline_ms = None
        if request.deadline_ms is not None:
            deadline_ms = float(request.deadline_ms)
            if not deadline_ms > 0:
                raise ValueError(
                    f"deadline_ms must be > 0 (or None); got "
                    f"{request.deadline_ms!r}")
        fut: Future = Future()
        if on_result is not None:
            fut.add_done_callback(
                lambda f, _cb=on_result, _req=request: _cb(_req, f))
        now = time.monotonic()
        expiry = None if deadline_ms is None else now + deadline_ms / 1e3
        entry = _Entry(request, fut, now, expiry)
        with self._cv:
            self._queue.push(entry)
            self._stats.submitted += 1
            t = request.tenant
            self._stats.tenant_submitted[t] = \
                self._stats.tenant_submitted.get(t, 0) + 1
            self._cv.notify()
        return fut

    def _validate_fields(self, request: Request) -> None:
        """Per-kind query-field validation (the shared tenant/priority/
        deadline metadata checks stay in ``submit``).  Scalar fast path
        with the same contract as ``validate_batch``."""
        n = self.engine.h.n
        kind = request.kind

        def _vertex(x) -> int:
            try:
                i = operator.index(x)
            except TypeError:
                raise ValueError(
                    f"request vertex ids must have an integer dtype; got "
                    f"{x!r}") from None
            if not 0 <= i < n:
                raise IndexError(
                    f"request vertex id {i} out of range [0, {n})")
            return i

        def _count(x, name: str) -> int:
            try:
                i = operator.index(x)
            except TypeError:
                raise ValueError(
                    f"request {name} must have an integer dtype; got "
                    f"{x!r}") from None
            if i < 1:
                raise ValueError(f"request {name} must be >= 1; got {i}")
            return i

        if kind == "mr_set":
            for name, ids in (("us", request.us), ("vs", request.vs)):
                if not ids:
                    raise ValueError(
                        f"mr_set request field {name!r} must be a non-empty "
                        f"vertex set")
                for x in ids:
                    _vertex(x)
            return
        if kind == "top_s":
            _vertex(request.u)
            _count(request.k, "k")
            return
        # every remaining kind is a (u, v) pair query
        try:
            u = operator.index(request.u)
            v = operator.index(request.v)
        except TypeError:
            raise ValueError(
                f"request vertex ids must have an integer dtype; got "
                f"({request.u!r}, {request.v!r})") from None
        if not 0 <= u < n or not 0 <= v < n:
            bad = u if not 0 <= u < n else v
            raise IndexError(
                f"request vertex id {bad} out of range [0, {n})")
        if kind in ("s_reach", "s_reach_k", "s_distance"):
            try:
                s = operator.index(request.s)
            except TypeError:
                raise ValueError(
                    f"request s must have an integer dtype; got "
                    f"{request.s!r}") from None
            if s < 1:
                raise ValueError(f"s-reachability needs s >= 1; got {s}")
        if kind == "s_reach_k":
            _count(request.k, "k")

    def submit_many(self, requests: Sequence[Request]) -> List[Future]:
        return [self.submit(r) for r in requests]

    def submit_stream(self, requests: Iterable[Request],
                      ) -> Iterator[Tuple[Request, Future]]:
        """Submit ``requests`` and yield ``(request, resolved_future)``
        pairs in *completion* order, as micro-batches finish — the
        long-poll client surface: a consumer iterates and sees each
        answer the moment its batch lands, not when the whole stream is
        done.  Futures arrive resolved; a deadline-expired request
        yields with ``DeadlineExceeded`` set rather than being dropped.

        In synchronous mode (``start=False``) the pending queue is
        drained inline after submission, so iteration still completes
        without a background thread."""
        done: "queue_mod.Queue[Tuple[Request, Future]]" = queue_mod.Queue()
        pairs = [(r, self.submit(
            r, on_result=lambda req, fut, _q=done: _q.put((req, fut))))
            for r in requests]
        if not self._running:
            self.drain()
        for _ in range(len(pairs)):
            yield done.get()

    def mr(self, u: int, v: int) -> Future:
        return self.submit(MRRequest(int(u), int(v)))

    def s_reach(self, u: int, v: int, s: int) -> Future:
        return self.submit(SReachRequest(int(u), int(v), int(s)))

    def witness(self, u: int, v: int) -> Future:
        return self.submit(WitnessRequest(int(u), int(v)))

    def s_reach_k(self, u: int, v: int, s: int, k: int) -> Future:
        return self.submit(SReachKRequest(int(u), int(v), int(s), int(k)))

    def mr_set(self, us: Iterable[int], vs: Iterable[int]) -> Future:
        return self.submit(MRSetRequest(tuple(int(x) for x in us),
                                        tuple(int(x) for x in vs)))

    def top_s(self, u: int, k: int) -> Future:
        return self.submit(TopSRequest(int(u), int(k)))

    def s_distance(self, u: int, v: int, s: int) -> Future:
        return self.submit(SDistanceRequest(int(u), int(v), int(s)))

    def update(self, inserts=(), deletes=()) -> None:
        """Apply hyperedge edits through the engine.  Serving continues:
        the stale resident snapshot keeps answering until the admission
        loop swaps in the refreshed one before the next micro-batch."""
        with self._dispatch_lock, self._stages.span("update"):
            self.engine.update(inserts, deletes)
            self._stats.updates += 1

    # -- durability (repro.store) ------------------------------------------

    def checkpoint(self, store) -> int:
        """Durably checkpoint the engine into ``store`` (a
        ``repro.store.IndexStore``) and attach the store as the engine's
        WAL sink — every subsequent ``update`` then journals (fsync)
        before applying, so a crash at any point is recoverable via
        ``restore``.  Runs under the dispatch lock, never mid-batch.
        Returns the checkpointed engine version."""
        with self._dispatch_lock:
            store.checkpoint(self.engine)
            store.attach(self.engine)
            return int(self.engine.version)

    @classmethod
    def restore(cls, store_or_path, *, mesh=None,
                axes: Optional[Tuple[str, str]] = None, verify: bool = True,
                expect_backend: Optional[str] = None,
                **service_opts) -> "ReachabilityService":
        """Warm-restart serving from a ``repro.store`` artifact (an
        ``IndexStore`` instance, a store directory, or a single
        ``save_index`` file): the checkpoint loads mmap-backed — no
        construction — the WAL suffix replays, the store re-attaches as
        the WAL sink, and the service starts around the restored engine.
        The engine arrives at its persisted version, so the first
        micro-batch installs a resident snapshot keyed to exactly that
        version — the same version-keyed swap a live ``update`` takes."""
        from repro.store import IndexStore, restore_engine
        if isinstance(store_or_path, IndexStore):
            engine = store_or_path.restore(mesh=mesh, verify=verify,
                                           expect_backend=expect_backend)
        else:
            engine = restore_engine(store_or_path, mesh=mesh, verify=verify,
                                    expect_backend=expect_backend)
        return cls(engine, mesh=mesh, axes=axes, **service_opts)

    def stats(self) -> ServiceStats:
        with self._dispatch_lock:
            return dataclasses.replace(
                self._stats,
                bucket_histogram=dict(self._stats.bucket_histogram),
                tenant_submitted=dict(self._stats.tenant_submitted),
                tenant_answered=dict(self._stats.tenant_answered),
                tenant_expired=dict(self._stats.tenant_expired),
                workload_answered=dict(self._stats.workload_answered),
                join_form_groups=dict(self._stats.join_form_groups),
                stages=self._stages.totals())

    def pending(self) -> int:
        with self._cv:
            return len(self._queue)

    def backlog(self) -> Dict[str, int]:
        """Pending request count per tenant."""
        with self._cv:
            return self._queue.backlog()

    # -- admission loop ----------------------------------------------------

    def _loop(self) -> None:
        stages = self._stages
        while True:
            with self._cv:
                if self._running and not len(self._queue):
                    with stages.span("wait"):
                        while self._running and not len(self._queue):
                            self._cv.wait(timeout=0.05)
                if not self._running and not len(self._queue):
                    return
                if self.max_wait_s > 0:
                    # linger for the full coalescing window (each submit()
                    # notify wakes the wait, so loop until the deadline or
                    # a full batch) — the latency/throughput admission knob
                    with stages.span("linger"):
                        deadline = time.monotonic() + self.max_wait_s
                        while (self._running
                                and len(self._queue) < self.max_batch):
                            remaining = deadline - time.monotonic()
                            if remaining <= 0:
                                break
                            self._cv.wait(timeout=remaining)
            seq, batch, _ = self._take()
            if batch:
                self._dispatch(batch, seq)

    def drain(self, max_batches: Optional[int] = None) -> int:
        """Synchronously dispatch pending requests in the caller's
        thread; returns the number of requests resolved (answered or
        deadline-failed).  This is the deterministic serving mode
        (``start=False``).  ``max_batches`` bounds the number of
        micro-batches taken — the fairness tests step one batch at a
        time to observe its composition."""
        total = 0
        batches = 0
        while max_batches is None or batches < max_batches:
            seq, batch, expired = self._take()
            if not batch and not expired:
                return total
            if batch:
                self._dispatch(batch, seq)
                batches += 1
            total += len(batch) + len(expired)
        return total

    def _take(self) -> Tuple[int, List[_Entry], List[_Entry]]:
        """Select the next micro-batch off the queue and fail the
        requests whose deadline passed.  Returns the take's number, the
        batch and the expired entries.  Adds the batch's time in the
        queue to ``queue_wait_s``: one subtraction per request."""
        self._batch_seq += 1
        seq = self._batch_seq
        with self._stages.span("take", batch=seq) as span:
            with self._cv:
                now = time.monotonic()
                batch, expired = self._queue.take(self.max_batch, now)
            waited = 0.0
            for entry in batch:
                waited += now - entry.enqueued
            if batch:
                with self._dispatch_lock:
                    self._stats.queued += len(batch)
                    self._stats.queue_wait_s += waited
            self._fail_expired(expired)
            span.set(taken=len(batch), wait_s=waited)
        return seq, batch, expired

    def _fail_expired(self, expired: List[_Entry]) -> None:
        if not expired:
            return
        now = time.monotonic()
        with self._dispatch_lock:
            self._stats.expired += len(expired)
            for entry in expired:
                t = entry.request.tenant
                self._stats.tenant_expired[t] = \
                    self._stats.tenant_expired.get(t, 0) + 1
        for entry in expired:
            waited_ms = (now - entry.enqueued) * 1e3
            try:
                entry.future.set_exception(
                    DeadlineExceeded(entry.request, waited_ms))
            except InvalidStateError:
                pass                 # cancelled while queued: drop quietly

    # -- dispatch ----------------------------------------------------------

    def _dispatch(self, batch: List[_Entry], seq: int) -> None:
        try:
            with self._stages.span("dispatch", batch=seq), \
                    self._dispatch_lock:
                with self._stages.span("refresh"):
                    snap = self._refresh_snapshot()
                groups: Dict[str, List[_Entry]] = {}
                for entry in batch:
                    groups.setdefault(entry.request.kind, []).append(entry)
                for kind, group in groups.items():
                    self._dispatch_group(kind, group, snap)
                self._stats.answered += len(batch)
                for entry in batch:
                    t = entry.request.tenant
                    self._stats.tenant_answered[t] = \
                        self._stats.tenant_answered.get(t, 0) + 1
        except Exception as exc:                       # noqa: BLE001
            for entry in batch:
                if not entry.future.done():
                    entry.future.set_exception(exc)

    def _dispatch_group(self, kind: str, group: List[_Entry], snap) -> None:
        if kind in _KIND_TO_OP:
            self._dispatch_workload_group(kind, group)
            return
        stages = self._stages
        q = len(group)
        bucket = _bucket_size(q, self.min_bucket, self.max_batch)
        with stages.span("prepare", kind=kind, q=q, bucket=bucket):
            us = np.fromiter((e.request.u for e in group), np.int64, q)
            vs = np.fromiter((e.request.v for e in group), np.int64, q)
            if bucket > q:
                # pad with a repeat of the first (real, validated) pair —
                # inert: answers past q are dropped before the scatter
                us = np.concatenate([us, np.full(bucket - q, us[0])])
                vs = np.concatenate([vs, np.full(bucket - q, vs[0])])
            if kind != "mr":
                svals = np.fromiter((e.request.s for e in group), np.int64,
                                    q)
            self._stats.batches += 1
            self._stats.padded_queries += bucket - q
            self._stats.bucket_histogram[bucket] = \
                self._stats.bucket_histogram.get(bucket, 0) + 1
            if isinstance(snap, KernelSnapshot):
                self._stats.kernel_batches += 1
            form = _join_form(snap)
            self._stats.join_form_groups[form] = \
                self._stats.join_form_groups.get(form, 0) + 1

        if kind == "mr":
            with stages.span("join", bucket=bucket, form=form):
                if snap is not None:
                    mr = np.asarray(snap.mr(us, vs))[:q]
                else:
                    mr = np.asarray(self.engine.mr_batch(us, vs))[:q]
            with stages.span("resolve", q=q):
                for entry, val in zip(group, mr):
                    _resolve(entry.future, int(val))
            return

        with stages.span("join", bucket=bucket, form=form):
            if snap is not None:
                # one fused join answers every s at once: s_reach == mr >= s
                ok = np.asarray(snap.mr(us, vs))[:q] >= svals
            elif svals.size and (svals == svals[0]).all():
                # uniform s: the backend's native (possibly cheaper) batch
                # path
                ok = np.asarray(
                    self.engine.s_reach_batch(us, vs, int(svals[0])))[:q]
            else:
                ok = np.asarray(self.engine.mr_batch(us, vs))[:q] >= svals
        with stages.span("resolve", q=q):
            for entry, val in zip(group, ok):
                _resolve(entry.future, bool(val))

    def _dispatch_workload_group(self, kind: str, group: List[_Entry]) -> None:
        """Workload kinds dispatch per-request through the engine's
        workload methods — witness reconstruction and the BFS-gated ops
        are host-side, while ``mr_set`` / ``top_s`` batch internally
        through ``mr_batch`` (which serves the kernel path when the
        engine enables it).  Each kind still arrives as its own group
        (bucket stream), so workload traffic never perturbs the padded
        mr/s_reach bucket shapes or their compiled-program count."""
        eng = self.engine
        self._stats.batches += 1
        self._stats.workload_answered[kind] = \
            self._stats.workload_answered.get(kind, 0) + len(group)
        for entry in group:
            r = entry.request
            if kind == "witness":
                val = eng.mr_witness(r.u, r.v)
            elif kind == "s_reach_k":
                val = bool(eng.s_reach_k(r.u, r.v, r.s, r.k))
            elif kind == "mr_set":
                val = int(eng.mr_set(np.asarray(r.us, np.int64),
                                     np.asarray(r.vs, np.int64)))
            elif kind == "top_s":
                verts, vals = eng.top_s(r.u, r.k)
                val = tuple(zip(verts.tolist(), vals.tolist()))
            else:                    # s_distance (admission pinned kinds)
                val = int(eng.s_distance(r.u, r.v, r.s))
            _resolve(entry.future, val)

    # -- snapshot lifecycle ------------------------------------------------

    def _refresh_snapshot(self):
        """The version-keyed snapshot swap, run between micro-batches
        (callers hold ``_dispatch_lock``).  Returns the resident serving
        snapshot, or None for snapshot-less backends."""
        eng = self.engine
        if self._snapshot_ok is False:
            return None
        if self._snap is not None and self._snap.version == eng.version:
            return self._serving_view()
        prev_host = self._host_snap
        try:
            # the fan-out hook: fresh snapshot + the row delta relative
            # to prev_host (None if the delta is unknowable and we must
            # re-land in full)
            host, dirty = eng.snapshot_delta(prev_host)
        except SnapshotUnsupported:
            self._snapshot_ok = False
            return None
        self._snapshot_ok = True
        if host is prev_host and self._snap is not None:
            return self._serving_view()
        self._stats.snapshot_refreshes += 1
        self._stats.rows_rederived += int(eng.last_snapshot_refresh_rows)
        self._stats.rows_full += int(eng.h.n)
        if self.mesh is not None and not self._already_on_mesh(host):
            base = self._snap if (prev_host is not None
                                  and dirty is not None) else None
            # base is private to the service and dropped at the swap, so
            # its buffers are safe to donate (in-place patch on device)
            snap = host.to_mesh(self.mesh, self.axes, base=base,
                                dirty_rows=dirty if base is not None
                                else None, donate_base=True)
            if base is not None and snap.ranks.shape == base.ranks.shape:
                self._stats.mesh_rows_patched += int(np.asarray(dirty).size)
        else:
            snap = host
        # single reference assignment = the atomic swap; in-flight code
        # never observes a half-updated snapshot
        self._host_snap, self._snap = host, snap
        return self._serving_view()

    def _serving_view(self):
        """The view micro-batches answer through: the resident snapshot,
        or — with ``use_kernels`` — a ``KernelSnapshot`` wrapper over it,
        rebuilt at every swap (so a re-landed or patched resident copy
        can never be served through a stale wrapper).  The wrapper
        shares this service's admission buckets, which is what bounds
        kernel-program count to one per bucket shape."""
        if not self.use_kernels or self._snap is None:
            return self._snap
        kv = self._kernel_snap
        if kv is None or kv.base is not self._snap:
            kv = KernelSnapshot(self._snap, min_bucket=self.min_bucket)
            self._kernel_snap = kv
        return kv

    def _already_on_mesh(self, snap) -> bool:
        """True when the engine's snapshot is already sharded over this
        service's mesh (the ``sharded`` backend derives mesh-resident
        snapshots) — re-landing it through ``to_mesh`` would gather the
        whole label mass to host and keep a duplicate device copy."""
        try:
            from jax.sharding import NamedSharding
            sharding = snap.ranks.sharding
        except Exception:                              # noqa: BLE001
            return False
        return (isinstance(sharding, NamedSharding)
                and sharding.mesh == self.mesh)
