"""Public facade for hypergraph reachability — the one import surface.

    from repro.api import build_engine, random_hypergraph

    h = random_hypergraph(1000, 1500)
    eng = build_engine(h, backend="auto", batch_hint=10_000)
    eng.mr(u, v)                     # scalar max-reachability
    eng.s_reach(u, v, s)             # scalar s-reachability
    eng.mr_batch(us, vs)             # [Q] vectorized
    snap = eng.snapshot()            # device-resident padded form
    snap.mr(us, vs)                  # fused XLA batch join

Every backend (see ``available_backends()``) answers through the same
``ReachabilityEngine`` protocol; ``backend="auto"`` lets the planner
(``plan_backend``) pick: ``hl-index`` while the label build is cheap;
past that, ``closure`` when its device working set (the overlap matrix,
the ``[n, m]`` closure rows and one full join) fits three quarters of
the device's reported memory and its rows build (about n·m² per round)
fits ``CLOSURE_BUILD_BUDGET``, the rows then being propagated on the
device; else the index-free ``frontier`` (batches) or ``online``.
Examples, benchmarks, and the cross-validation suite all route through
this module, so a new backend is one ``register_backend`` entry away from
being benchmarked and validated.

Hyperedge updates go through the same engine — no rebuilding by hand:

    eng.update(inserts=[[3, 7, 9]], deletes=[4])   # in place
    eng.mr(u, v)                     # answers == full rebuild
    snap2 = eng.snapshot()           # fresh (the old snapshot is stale:
                                     #  snap.version != eng.version)

``update_capabilities()`` maps each backend to how it absorbs updates:
scoped construction on the affected line-graph component(s)
(``hl-index``/``hl-index-basic``), 1-hop adjacency-cache patches
(``online``/``frontier``), whole-structure recompute behind the same
call (``closure``/``sharded``), or ``UpdateUnsupported`` (the static
baselines).

Indexes survive the process that built them (``repro.store``):

    save_index("paper.hlidx", eng)               # versioned, checksummed
    eng2 = build_engine(restore="paper.hlidx")   # mmap load, no rebuild
    store = IndexStore("idx.d", checkpoint_every=64)
    store.attach(eng)                            # WAL: updates journal first
    eng3 = build_engine(restore="idx.d")         # checkpoint + WAL replay

Serving heavy request traffic goes through the request-based service
instead of hand-assembled batches (``repro.serve.reach_service``);
serving knobs live in a typed ``ServiceConfig``:

    svc = serve(h, batch_hint=10_000,            # engine + admission loop
                config=ServiceConfig(max_batch=1024))
    f = svc.mr(4, 8)                             # Future[int]
    g = svc.submit(SReachRequest(4, 8, s=2))     # Future[bool], mixed s ok
    f.result(); g.result()
    svc.update(inserts=[[3, 7, 9]])              # snapshot swapped between
    svc.close()                                  #   micro-batches

The service coalesces pending requests into fused padded device batches
(power-of-two buckets bound XLA recompiles) and reuses one
version-keyed resident snapshot across batches — after a scoped update
only the dirty label rows are re-derived.

The service is multi-tenant: requests carry ``tenant`` / ``priority`` /
``deadline_ms`` metadata, the admission queue is weighted-fair across
tenants within strict priority bands, expired requests fail fast with
``DeadlineExceeded``, and ``submit_stream`` delivers answers in
completion order.  ``ServiceConfig(replicas=N)`` scales reads by
serving round-robin off N mesh-resident snapshot replicas
(``ReplicaGroup``) — updates apply on the single writer and only the
dirty label rows fan out to the replicas:

    svc = serve(h, config=ServiceConfig(
        tenants=(TenantSpec("analytics", weight=1.0),
                 TenantSpec("dashboard", weight=4.0)),
        replicas=2))
    svc.submit(MRRequest(4, 8, tenant="dashboard",
                         priority="interactive", deadline_ms=50.0))

Beyond point mr/s-reach, the engines answer five *workload* query
families (``repro.workloads``; per-backend support in
``workload_capabilities()``, unsupported ops raise
``WorkloadUnsupported``):

    eng.mr_witness(u, v)             # Witness: the hyperedge walk itself
    eng.s_reach_k(u, v, s, k)        # s-walk of <= k hyperedges?
    eng.mr_set(us, vs)               # set-to-set max MR (batched join)
    eng.mr_from_set(us, targets)     # multi-source MR per target
    eng.top_s(u, k)                  # k strongest-s neighbors of u
    eng.s_distance(u, v, s)          # certified landmark upper bound

The same families serve as typed requests (``WitnessRequest``,
``SReachKRequest``, ``MRSetRequest``, ``TopSRequest``,
``SDistanceRequest``) through ``serve()`` — same tenant/priority/
deadline metadata, own dispatch groups, refused at admission when the
backend lacks the capability.

Multi-device serving goes through the same two calls — build a mesh and
pass it:

    from repro.api import build_engine, make_mesh

    mesh = make_mesh((2, 2), ("data", "model"))   # or any device grid
    eng = build_engine(h, backend="auto", mesh=mesh)   # planner may pick
    eng = build_engine(h, backend="sharded", mesh=mesh, schedule="ring")
    eng.mr_batch(us, vs)             # served off the block-sharded W*

Index **construction** itself shards over the mesh too — the one stage
that used to be host-serial (see ``repro.core.hlindex.build_sharded``):

    eng = build_engine(h, "hl-index", mesh=mesh)      # auto: sharded build
    eng = build_engine(h, "hl-index", construction="sharded", workers=4)
    eng = build_engine(h, "sharded", mesh=mesh, build_labels=True)

The sharded builder partitions the rank-ordered root sequence at
line-graph component boundaries, precomputes the shared neighbor index
as one CSR (overlaps on the mesh when one is passed), and merges with a
deterministic reconciliation pass — labels are byte-identical to the
serial ``build_fast`` (property-tested), so every downstream contract
(scoped maintenance splice, dirty-rows snapshot caching, serving) is
unchanged.  ``build_labels=True`` flips the ``sharded`` backend from the
resident-closure regime to serving mesh-sharded label snapshots.

``make_mesh`` (re-exported from ``repro.compat``) builds a mesh whose
axes are all ``AxisType.Auto``; ``snap.to_mesh(mesh)`` re-lands any
label snapshot sharded over a mesh.  The architecture — data flow,
backend catalogue, planner policy, construction modes, and the
sharding schedules — is documented in ``docs/ARCHITECTURE.md``.
"""
from __future__ import annotations

import dataclasses
import warnings

from repro.compat import make_mesh
from repro.core.engine import (ReachabilityEngine, DeviceSnapshot,
                               SnapshotUnsupported, UpdateUnsupported,
                               WorkloadUnsupported, WORKLOAD_OPS,
                               available_backends, update_capabilities,
                               workload_capabilities, plan_backend,
                               register_backend, validate_batch)
from repro.core.engine import build as build_engine
from repro.core.hypergraph import (Hypergraph, from_edge_lists, compact,
                                   random_hypergraph,
                                   planted_chain_hypergraph,
                                   colocation_hypergraph, paper_figure1)
from repro.serve.reach_service import (MRRequest, MRSetRequest,
                                       ReachabilityService, Request,
                                       SDistanceRequest, ServiceConfig,
                                       SReachKRequest, SReachRequest,
                                       TopSRequest, WitnessRequest)
from repro.serve.replicas import ReplicaGroup
from repro.serve.scheduler import (PRIORITY_CLASSES, DeadlineExceeded,
                                   TenantSpec)
from repro.store import (IndexStore, load_index, read_hif, save_index,
                         write_hif)
from repro.workloads import DistanceOracle, Witness, verify_witness

__all__ = [
    "ReachabilityEngine", "DeviceSnapshot", "SnapshotUnsupported",
    "UpdateUnsupported", "build_engine", "available_backends",
    "update_capabilities", "plan_backend", "register_backend",
    "validate_batch", "make_mesh",
    "ReachabilityService", "ReplicaGroup", "serve", "ServiceConfig",
    "TenantSpec", "PRIORITY_CLASSES", "DeadlineExceeded",
    "Request", "MRRequest", "SReachRequest",
    # workload surface: one pinned set — engine capabilities, request
    # kinds, and the answer/verification types
    "WorkloadUnsupported", "WORKLOAD_OPS", "workload_capabilities",
    "WitnessRequest", "SReachKRequest", "MRSetRequest", "TopSRequest",
    "SDistanceRequest", "Witness", "verify_witness", "DistanceOracle",
    "Hypergraph", "from_edge_lists", "compact", "random_hypergraph",
    "planted_chain_hypergraph", "colocation_hypergraph", "paper_figure1",
    "IndexStore", "save_index", "load_index", "read_hif", "write_hif",
]

# service knobs that used to ride along in serve(**opts); still accepted
# for one release through the deprecation shim below
_LEGACY_SERVICE_KWARGS = ("max_batch", "min_bucket", "max_wait_ms",
                          "axes", "use_kernels")


def serve(h_or_engine, backend: str = "auto", *,
          config: ServiceConfig = None, mesh=None,
          start: bool = True, batch_hint=None,
          **opts) -> ReachabilityService:
    """One-call serving: build an engine (unless given one) and wrap it
    in a ``ReachabilityService`` (or, with ``config.replicas > 1``, a
    ``ReplicaGroup``).

    Args:
      h_or_engine: a ``Hypergraph`` to build an engine over, or an
        already-built ``ReachabilityEngine`` to serve as-is.
      config: a ``ServiceConfig`` — the typed home of every serving knob
        (batching, tenant weights, priorities, replicas; see its
        docstring).  Defaults to ``ServiceConfig()``.
      backend / batch_hint / mesh / engine ``**opts``: forwarded to
        ``build_engine`` when a hypergraph is passed.  ``mesh`` is also
        handed to the service so the resident snapshot is kept
        mesh-sharded.  With ``backend="auto"`` the planner reserves
        room for a join of ``config.max_batch`` queries.
      start: start the background admission thread (``start=False`` =
        synchronous mode; call ``svc.drain()``).

    ``config.axes`` names the mesh (row, column) axes in both layers
    and is forwarded to both: the ``sharded`` engine's block-sharding
    and the service's ``to_mesh`` re-landing.  ``config.use_kernels``
    is likewise two-layer: it reaches the engine build (Pallas
    closure/batch paths, for backends that take it) and the service
    (Pallas label-join serving view) — with a prebuilt engine it
    configures the service alone.

    Deprecated: the service knobs (``max_batch``, ``min_bucket``,
    ``max_wait_ms``, ``axes``, ``use_kernels``) are still accepted as
    bare keyword arguments for one release — they fold into ``config``
    with a ``DeprecationWarning``.  Everything else in ``**opts`` is an
    engine-build option.
    """
    legacy = {k: opts.pop(k) for k in _LEGACY_SERVICE_KWARGS if k in opts}
    cfg = config if config is not None else ServiceConfig()
    if legacy:
        warnings.warn(
            f"passing service options {sorted(legacy)} to serve() as bare "
            f"keyword arguments is deprecated; pass "
            f"config=ServiceConfig(...) instead",
            DeprecationWarning, stacklevel=2)
        cfg = dataclasses.replace(cfg, **legacy)
    if isinstance(h_or_engine, Hypergraph):
        if cfg.use_kernels is not None:
            opts["use_kernels"] = cfg.use_kernels
        # resolve "auto" here so backend-specific options route correctly
        # (axes must reach the sharded engine even when the planner — not
        # the caller — picked it)
        resolved = backend if backend != "auto" else plan_backend(
            h_or_engine, batch_hint, mesh=mesh,
            device_budget_bytes=opts.get("device_budget_bytes"),
            join_batch=cfg.max_batch)
        if cfg.axes is not None and resolved == "sharded":
            opts["axes"] = cfg.axes  # same axes in both layers
        engine = build_engine(h_or_engine, resolved, batch_hint=batch_hint,
                              mesh=mesh, **opts)
    else:
        rejected = sorted(opts)
        if backend != "auto":
            rejected.append(f"backend={backend!r}")
        if batch_hint is not None:
            rejected.append(f"batch_hint={batch_hint!r}")
        if rejected:
            raise ValueError(
                f"engine options {rejected} make no sense with an "
                f"already-built engine — they would be silently ignored")
        engine = h_or_engine
    if cfg.replicas > 1:
        return ReplicaGroup(engine, config=cfg, mesh=mesh, start=start)
    return ReachabilityService(engine, config=cfg, mesh=mesh, start=start)
