"""Multi-tenant serving: weighted-fair scheduling, priority classes,
deadlines, streaming delivery, and read-replica fan-out.

The fairness/starvation tests run the service in synchronous mode and
step it one micro-batch at a time (``drain(max_batches=1)``) so each
batch's *composition* is observable and the asserted bounds are exact,
not timing-dependent.  Replica tests pin every replica byte-identical
to the writer's snapshot at every version, and every answer anywhere is
pinned to the independent MSTOracle.
"""
import dataclasses
import time

import numpy as np
import pytest

from repro.api import (DeadlineExceeded, MRRequest, PRIORITY_CLASSES,
                       ReachabilityService, ReplicaGroup, Request,
                       ServiceConfig, SReachRequest, TenantSpec,
                       build_engine, random_hypergraph, serve)
from repro.core import MSTOracle
from repro.core.distributed import default_line_graph_mesh
from repro.core.engine import SnapshotUnsupported
from repro.serve.record import RequestFuture
from repro.serve.scheduler import WeightedFairScheduler


def _entry(req, expiry=None, now=0.0):
    return RequestFuture(req, now, expiry)


def _oracle_check(h, reqs, futs):
    oracle = MSTOracle(h)
    for r, f in zip(reqs, futs):
        mr = oracle.mr(r.u, r.v)
        want = mr if r.kind == "mr" else mr >= r.s
        assert f.result(timeout=60) == want


# ---------------------------------------------------------------------------
# typed config / request surface
# ---------------------------------------------------------------------------

def test_tenant_spec_validation():
    spec = TenantSpec("analytics", 3)
    assert spec.weight == 3.0 and isinstance(spec.weight, float)
    with pytest.raises(ValueError, match="non-empty"):
        TenantSpec("")
    with pytest.raises(ValueError, match="weight"):
        TenantSpec("x", 0.0)
    with pytest.raises(ValueError, match="weight"):
        TenantSpec("x", -1.0)
    with pytest.raises(dataclasses.FrozenInstanceError):
        spec.weight = 2.0


def test_service_config_validation():
    cfg = ServiceConfig(max_batch="64", min_bucket=4.0)
    assert cfg.max_batch == 64 and cfg.min_bucket == 4
    with pytest.raises(ValueError, match="min_bucket"):
        ServiceConfig(min_bucket=64, max_batch=8)
    with pytest.raises(ValueError, match="replicas"):
        ServiceConfig(replicas=0)
    with pytest.raises(ValueError, match="quantum"):
        ServiceConfig(quantum=0)
    with pytest.raises(ValueError, match="default_weight"):
        ServiceConfig(default_weight=0)
    with pytest.raises(TypeError, match="TenantSpec"):
        ServiceConfig(tenants=("not-a-spec",))
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg.max_batch = 128


def test_request_base_defaults_preserve_old_behavior():
    # positional construction means what it always meant
    r = MRRequest(4, 8)
    assert (r.u, r.v) == (4, 8)
    assert r.tenant == "default" and r.priority == "standard"
    assert r.deadline_ms is None
    assert r == MRRequest(4, 8, tenant="default", priority="standard",
                          deadline_ms=None)
    s = SReachRequest(4, 8, 2)
    assert (s.u, s.v, s.s) == (4, 8, 2)
    assert isinstance(r, Request) and isinstance(s, Request)
    with pytest.raises(dataclasses.FrozenInstanceError):
        r.u = 3
    r2 = dataclasses.replace(r, tenant="t", priority="interactive")
    assert (r2.u, r2.v, r2.tenant, r2.priority) == (4, 8, "t", "interactive")
    # the metadata fields live on the base — what docs check 8 pins
    assert {f.name for f in dataclasses.fields(Request)} == \
        {"tenant", "priority", "deadline_ms"}


def test_submit_validates_metadata():
    h = random_hypergraph(20, 25, seed=0)
    svc = serve(h, "hl-index", start=False)
    with pytest.raises(ValueError, match="priority"):
        svc.submit(MRRequest(1, 2, priority="urgent"))
    with pytest.raises(ValueError, match="tenant"):
        svc.submit(MRRequest(1, 2, tenant=""))
    with pytest.raises(ValueError, match="deadline_ms"):
        svc.submit(MRRequest(1, 2, deadline_ms=0))
    with pytest.raises(ValueError, match="deadline_ms"):
        svc.submit(MRRequest(1, 2, deadline_ms=-5.0))
    assert svc.pending() == 0        # nothing invalid was enqueued


# ---------------------------------------------------------------------------
# scheduler unit tests (policy in isolation)
# ---------------------------------------------------------------------------

def test_scheduler_weighted_shares_exact():
    sched = WeightedFairScheduler((TenantSpec("a", 1.0), TenantSpec("b", 3.0)),
                                  quantum=8)
    for i in range(100):
        sched.push(_entry(MRRequest(0, 1, tenant="a")))
        sched.push(_entry(MRRequest(0, 1, tenant="b")))
    selected, expired = sched.take(64, now=0.0)
    assert not expired and len(selected) == 64
    counts = {}
    for e in selected:
        counts[e.request.tenant] = counts.get(e.request.tenant, 0) + 1
    # DRR with quantum 8: per pass a banks 8 credits, b banks 24 — a
    # 64-slot batch is exactly two passes
    assert counts == {"a": 16, "b": 48}
    assert len(sched) == 136
    assert sched.backlog() == {"a": 84, "b": 52}


def test_scheduler_priority_bands_strict():
    sched = WeightedFairScheduler()
    for i in range(50):
        sched.push(_entry(MRRequest(0, 1, tenant="g", priority="batch")))
    for i in range(5):
        sched.push(_entry(MRRequest(0, 1, tenant="s", priority="standard")))
    for i in range(3):
        sched.push(_entry(MRRequest(0, 1, tenant="i", priority="interactive")))
    selected, _ = sched.take(32, now=0.0)
    prios = [e.request.priority for e in selected]
    # strict bands: all interactive, then all standard, then batch fill
    assert prios[:3] == ["interactive"] * 3
    assert prios[3:8] == ["standard"] * 5
    assert prios[8:] == ["batch"] * 24
    # fairness never leaves bucket slots idle under backlog
    assert len(selected) == 32


def test_scheduler_expired_swept_without_consuming_share():
    sched = WeightedFairScheduler()
    for i in range(10):
        sched.push(_entry(MRRequest(0, 1, tenant="a"), expiry=1.0))
    for i in range(10):
        sched.push(_entry(MRRequest(0, 1, tenant="a"), expiry=None))
    selected, expired = sched.take(64, now=2.0)
    assert len(expired) == 10 and len(selected) == 10
    assert all(e.expiry == 1.0 for e in expired)
    assert len(sched) == 0


# ---------------------------------------------------------------------------
# adversarial fairness through the service
# ---------------------------------------------------------------------------

def test_flooding_tenant_cannot_starve_light_tenant():
    h = random_hypergraph(40, 60, seed=1)
    cfg = ServiceConfig(max_batch=64, tenants=(TenantSpec("greedy", 1.0),
                                               TenantSpec("light", 1.0)))
    svc = serve(h, "hl-index", config=cfg, start=False)
    rng = np.random.default_rng(0)
    flood = [MRRequest(int(rng.integers(h.n)), int(rng.integers(h.n)),
                       tenant="greedy") for _ in range(2000)]
    greedy_futs = svc.submit_many(flood)
    light = [MRRequest(int(rng.integers(h.n)), int(rng.integers(h.n)),
                       tenant="light") for _ in range(5)]
    light_futs = svc.submit_many(light)
    # the weighted-fair bound: the light tenant waits at most ONE
    # micro-batch behind a 2000-deep adversarial flood
    svc.drain(max_batches=1)
    assert all(f.done() for f in light_futs)
    _oracle_check(h, light, light_futs)
    svc.drain()
    _oracle_check(h, flood, greedy_futs)
    st = svc.stats()
    assert st.tenant_answered == {"greedy": 2000, "light": 5}
    assert st.expired == 0


def test_weighted_shares_shape_every_batch():
    h = random_hypergraph(40, 60, seed=2)
    cfg = ServiceConfig(max_batch=64, quantum=8,
                        tenants=(TenantSpec("a", 1.0), TenantSpec("b", 3.0)))
    svc = serve(h, "hl-index", config=cfg, start=False)
    rng = np.random.default_rng(1)
    for _ in range(600):
        svc.submit(MRRequest(int(rng.integers(h.n)), int(rng.integers(h.n)),
                             tenant="a"))
        svc.submit(MRRequest(int(rng.integers(h.n)), int(rng.integers(h.n)),
                             tenant="b"))
    prev = {"a": 0, "b": 0}
    # while both tenants stay backlogged, every batch splits 1:3 exactly
    for _ in range(5):
        svc.drain(max_batches=1)
        st = svc.stats()
        got = {t: st.tenant_answered[t] - prev[t] for t in ("a", "b")}
        assert got == {"a": 16, "b": 48}
        prev = dict(st.tenant_answered)
    svc.drain()
    assert svc.stats().tenant_answered == {"a": 600, "b": 600}


def test_priority_inversion_bounded():
    h = random_hypergraph(40, 60, seed=3)
    svc = serve(h, "hl-index", config=ServiceConfig(max_batch=64),
                start=False)
    rng = np.random.default_rng(2)
    flood = [MRRequest(int(rng.integers(h.n)), int(rng.integers(h.n)),
                       tenant="greedy", priority="batch")
             for _ in range(500)]
    svc.submit_many(flood)
    probe = MRRequest(3, 7, tenant="dash", priority="interactive")
    probe_fut = svc.submit(probe)
    svc.drain(max_batches=1)
    # the interactive probe rides the very next batch despite arriving
    # behind 500 batch-class requests
    assert probe_fut.done()
    _oracle_check(h, [probe], [probe_fut])
    svc.drain()


# ---------------------------------------------------------------------------
# deadlines
# ---------------------------------------------------------------------------

def test_deadline_expiry_fails_fast_with_typed_error():
    h = random_hypergraph(30, 40, seed=4)
    svc = serve(h, "hl-index", start=False)
    doomed = MRRequest(1, 2, deadline_ms=1.0)
    doomed_fut = svc.submit(doomed)
    live = MRRequest(3, 4)
    live_fut = svc.submit(live)
    time.sleep(0.02)
    resolved = svc.drain()
    assert resolved == 2             # answered + deadline-failed both count
    with pytest.raises(DeadlineExceeded) as err:
        doomed_fut.result()
    assert err.value.request is doomed
    assert err.value.waited_ms >= 1.0
    _oracle_check(h, [live], [live_fut])
    st = svc.stats()
    assert st.expired == 1 and st.tenant_expired == {"default": 1}
    assert st.tenant_answered == {"default": 1}


def test_generous_deadline_is_met():
    h = random_hypergraph(30, 40, seed=5)
    svc = serve(h, "hl-index", start=False)
    reqs = [MRRequest(i, i + 1, deadline_ms=60_000.0) for i in range(10)]
    futs = svc.submit_many(reqs)
    svc.drain()
    _oracle_check(h, reqs, futs)
    assert svc.stats().expired == 0


# ---------------------------------------------------------------------------
# streaming / callback delivery
# ---------------------------------------------------------------------------

def test_submit_stream_yields_resolved_futures_sync():
    h = random_hypergraph(30, 40, seed=6)
    svc = serve(h, "hl-index", start=False)
    reqs = [MRRequest(i, (i * 3) % h.n) if i % 2 else
            SReachRequest(i, (i * 3) % h.n, 2) for i in range(20)]
    got = list(svc.submit_stream(reqs))
    assert len(got) == 20
    assert all(f.done() for _, f in got)
    by_req = {id(r): f for r, f in got}
    _oracle_check(h, reqs, [by_req[id(r)] for r in reqs])


def test_submit_stream_threaded_completion_order():
    h = random_hypergraph(30, 40, seed=7)
    with serve(h, "hl-index", config=ServiceConfig(max_wait_ms=1.0)) as svc:
        reqs = [MRRequest(i, (i * 7) % h.n) for i in range(30)]
        got = list(svc.submit_stream(reqs))
    assert sorted(id(r) for r, _ in got) == sorted(id(r) for r in reqs)
    by_req = {id(r): f for r, f in got}
    _oracle_check(h, reqs, [by_req[id(r)] for r in reqs])


def test_on_result_callback_hook():
    h = random_hypergraph(30, 40, seed=8)
    svc = serve(h, "hl-index", start=False)
    seen = []
    reqs = [MRRequest(i, i + 2) for i in range(8)]
    futs = [svc.submit(r, on_result=lambda rq, f: seen.append((rq, f)))
            for r in reqs]
    svc.drain()
    assert len(seen) == 8
    assert {id(r) for r, _ in seen} == {id(r) for r in reqs}
    assert all(f.done() for _, f in seen)
    _oracle_check(h, reqs, futs)
    # the hook fires on failure paths too (deadline expiry)
    failed = []
    svc.submit(MRRequest(0, 1, deadline_ms=1.0),
               on_result=lambda rq, f: failed.append(f))
    time.sleep(0.01)
    svc.drain()
    assert len(failed) == 1 and isinstance(failed[0].exception(),
                                           DeadlineExceeded)


# ---------------------------------------------------------------------------
# replica fan-out
# ---------------------------------------------------------------------------

def _assert_replicas_match_writer(grp):
    host = grp.engine.snapshot()     # cached/current: no re-derivation
    for r in grp.replicas:
        assert r.snap is not None and r.snap.version == host.version
        np.testing.assert_array_equal(np.asarray(r.snap.ranks),
                                      np.asarray(host.ranks))
        np.testing.assert_array_equal(np.asarray(r.snap.svals),
                                      np.asarray(host.svals))
        np.testing.assert_array_equal(np.asarray(r.snap.lengths),
                                      np.asarray(host.lengths))


def test_replica_group_churn_stays_byte_identical():
    # the multi-chain graph from the serving regression tests: chains A
    # and B absorb scoped edits while the long chain C pins the padded
    # geometry, so updates fan out as row patches (not full re-lands)
    from repro.core import from_edge_lists
    edges = [[0, 1, 2], [1, 2, 3],            # chain A
             [10, 11, 12], [11, 12, 13]]      # chain B
    for i in range(10):                        # chain C dominates lmax
        edges.append([20 + 2 * i, 21 + 2 * i, 22 + 2 * i, 23 + 2 * i])
    h = from_edge_lists(edges)
    eng = build_engine(h, "hl-index")
    grp = ReplicaGroup(eng, 3, mesh=default_line_graph_mesh(),
                       config=ServiceConfig(max_batch=32), start=False)
    rng = np.random.default_rng(3)
    edits = [[[0, 1, 2, 3]], [[10, 11, 12, 13]], [[0, 2, 3]], [[11, 13]]]
    for ins in edits:
        cur = grp.engine.h
        reqs = [MRRequest(int(rng.integers(cur.n)), int(rng.integers(cur.n)))
                for _ in range(80)]
        futs = grp.submit_many(reqs)
        grp.drain()
        _oracle_check(cur, reqs, futs)
        _assert_replicas_match_writer(grp)
        grp.update(inserts=ins)      # single writer; fan-out at next batch
    # post-churn: all replicas answer the updated graph correctly
    cur = grp.engine.h
    reqs = [MRRequest(int(rng.integers(cur.n)), int(rng.integers(cur.n)))
            for _ in range(80)]
    futs = grp.submit_many(reqs)
    grp.drain()
    _oracle_check(cur, reqs, futs)
    _assert_replicas_match_writer(grp)
    rstats = grp.replica_stats()
    # every replica served batches (round-robin) ...
    assert all(r["batches"] >= 1 for r in rstats)
    # ... was landed in full exactly once, and patched row-wise since
    assert all(r["full_relands"] == 1 for r in rstats)
    assert all(r["rows_patched"] > 0 for r in rstats)
    assert grp.stats().mesh_rows_patched == sum(r["rows_patched"]
                                                for r in rstats)


def test_replica_group_kernel_serving_matches_oracle():
    h = random_hypergraph(40, 60, seed=10)
    eng = build_engine(h, "hl-index")
    grp = ReplicaGroup(eng, 2, config=ServiceConfig(use_kernels=True,
                                                    max_batch=32),
                       start=False)
    rng = np.random.default_rng(4)
    reqs = [SReachRequest(int(rng.integers(h.n)), int(rng.integers(h.n)),
                          int(rng.integers(1, 4))) for _ in range(64)]
    futs = grp.submit_many(reqs)
    grp.drain()
    _oracle_check(h, reqs, futs)
    assert grp.stats().kernel_batches >= 1


def test_replica_group_refuses_snapshotless_backend():
    h = random_hypergraph(25, 35, seed=11)
    eng = build_engine(h, "online")
    with pytest.raises(SnapshotUnsupported, match="replica"):
        ReplicaGroup(eng, 2, start=False)


def test_plain_service_refuses_replicated_config():
    h = random_hypergraph(25, 35, seed=12)
    eng = build_engine(h, "hl-index")
    with pytest.raises(ValueError, match="ReplicaGroup"):
        ReachabilityService(eng, config=ServiceConfig(replicas=2),
                            start=False)


def test_serve_routes_replicated_config_to_group():
    h = random_hypergraph(30, 45, seed=13)
    svc = serve(h, "hl-index", config=ServiceConfig(replicas=2), start=False)
    assert isinstance(svc, ReplicaGroup) and len(svc.replicas) == 2
    reqs = [MRRequest(i % h.n, (i * 5) % h.n) for i in range(40)]
    futs = svc.submit_many(reqs)
    svc.drain()
    _oracle_check(h, reqs, futs)


# ---------------------------------------------------------------------------
# API redesign: deprecation shim + re-exports
# ---------------------------------------------------------------------------

def test_serve_legacy_kwargs_warn_and_still_work():
    h = random_hypergraph(25, 35, seed=14)
    with pytest.warns(DeprecationWarning, match="ServiceConfig"):
        svc = serve(h, "hl-index", start=False, max_batch=32, min_bucket=4)
    assert svc.max_batch == 32 and svc.min_bucket == 4
    f = svc.mr(1, 2)
    svc.drain()
    assert f.result() == MSTOracle(h).mr(1, 2)
    # legacy kwargs override the matching config field
    with pytest.warns(DeprecationWarning):
        svc2 = serve(h, "hl-index", start=False,
                     config=ServiceConfig(max_batch=128), max_batch=16)
    assert svc2.max_batch == 16


def test_config_path_does_not_warn():
    import warnings
    h = random_hypergraph(25, 35, seed=15)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        svc = serve(h, "hl-index", start=False,
                    config=ServiceConfig(max_batch=32))
    assert svc.max_batch == 32


def test_api_reexports_cover_multitenant_surface():
    import repro.api as api
    for name in ("Request", "MRRequest", "SReachRequest", "ServiceConfig",
                 "TenantSpec", "PRIORITY_CLASSES", "DeadlineExceeded",
                 "ReplicaGroup", "ReachabilityService", "serve"):
        assert name in api.__all__ and getattr(api, name) is not None
    import repro.serve as srv
    assert srv.WeightedFairScheduler is WeightedFairScheduler
    assert srv.PRIORITY_CLASSES == {"interactive": 0, "standard": 1,
                                    "batch": 2}
    assert PRIORITY_CLASSES["interactive"] < PRIORITY_CLASSES["standard"] \
        < PRIORITY_CLASSES["batch"]
