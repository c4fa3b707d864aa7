"""The serving loop's stage spans and queue-wait counters
(``ServiceStats.stages``, ``queued``, ``queue_wait_s``)."""
import time

import numpy as np
import pytest

from repro.api import (DeadlineExceeded, MRRequest, ServiceConfig,
                       SReachRequest, build_engine, random_hypergraph, serve)
from repro.serve.replicas import ReplicaGroup

DISPATCH_CHILDREN = ("refresh", "prepare", "join", "resolve")


def _mixed(n, count, seed=0):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(count):
        u, v = int(rng.integers(n)), int(rng.integers(n))
        reqs.append(MRRequest(u, v) if i % 2 else
                    SReachRequest(u, v, int(rng.integers(1, 4))))
    return reqs


@pytest.fixture(scope="module")
def engine():
    return build_engine(random_hypergraph(80, 120, seed=7), "hl-index")


def test_drained_service_counts_one_stage_run_per_group_and_take(engine):
    svc = serve(engine, config=ServiceConfig(max_batch=64, min_bucket=8),
                start=False)
    futs = svc.submit_many(_mixed(engine.h.n, 300))
    time.sleep(0.002)
    svc.drain()
    for f in futs:
        f.result(timeout=0)
    st = svc.stats()
    stages = st.stages
    takes = -(-300 // 64)                    # five full-or-partial takes
    assert stages["dispatch"].count == takes
    assert stages["refresh"].count == takes
    # both kinds in every take: one prepare, join and resolve per group
    for name in ("prepare", "join", "resolve"):
        assert stages[name].count == st.batches == 2 * takes
    # drain's last take finds the queue empty
    assert stages["take"].count == takes + 1
    assert st.queued == st.answered == 300
    assert st.queue_wait_s >= 300 * 0.002
    # synchronous mode neither waits nor lingers
    assert "wait" not in stages and "linger" not in stages
    inner = sum(stages[k].wall_s for k in DISPATCH_CHILDREN)
    assert inner <= stages["dispatch"].wall_s
    assert all(t.cpu_s <= t.cpu_wall_s + 1e-3 for t in stages.values())


def test_one_kind_dispatch_count_equals_batches(engine):
    svc = serve(engine, config=ServiceConfig(max_batch=32), start=False)
    futs = [svc.mr(i % engine.h.n, (3 * i) % engine.h.n) for i in range(100)]
    svc.drain()
    [f.result(timeout=0) for f in futs]
    st = svc.stats()
    assert st.stages["dispatch"].count == st.batches == 4
    assert st.queued == 100


def test_admission_thread_waits_lingers_and_takes(engine):
    svc = serve(engine, config=ServiceConfig(max_batch=64, max_wait_ms=1.0))
    try:
        for _ in range(3):
            futs = svc.submit_many(_mixed(engine.h.n, 40, seed=1))
            [f.result(timeout=30) for f in futs]
            time.sleep(0.02)
    finally:
        svc.close()
    st = svc.stats()
    assert st.stages["wait"].count >= 1
    assert st.stages["linger"].count >= 1
    assert st.stages["dispatch"].count >= 1
    assert st.queued == st.answered == 120
    # a waiting thread is asleep: its CPU time is a sliver of the wall
    assert st.stages["wait"].cpu_share < 0.5


def test_no_linger_span_without_a_coalescing_wait(engine):
    with serve(engine, config=ServiceConfig(max_wait_ms=0)) as svc:
        [f.result(timeout=30) for f in svc.submit_many(
            _mixed(engine.h.n, 20))]
    assert "linger" not in svc.stats().stages


def test_expired_requests_are_failed_in_the_take_and_not_queued(engine):
    svc = serve(engine, start=False)
    late = svc.submit(MRRequest(1, 2, deadline_ms=0.01))
    ok = svc.mr(3, 4)
    time.sleep(0.01)
    svc.drain()
    with pytest.raises(DeadlineExceeded):
        late.result(timeout=0)
    ok.result(timeout=0)
    st = svc.stats()
    assert st.expired == 1 and st.queued == 1


def test_update_is_timed_and_stats_carry_stage_copies(engine):
    h = random_hypergraph(40, 60, seed=9)
    svc = serve(build_engine(h, "hl-index"), start=False)
    svc.update(inserts=[[0, 1, 2]])
    futs = svc.submit_many(_mixed(h.n, 10))
    svc.drain()
    [f.result(timeout=0) for f in futs]
    st = svc.stats()
    assert st.stages["update"].count == 1
    before = st.stages["dispatch"].count
    svc.submit(MRRequest(0, 1))
    svc.drain()
    assert st.stages["dispatch"].count == before       # a copy
    d = svc.stats().as_dict()["stages"]
    assert set(d["join"]) == {"count", "wall_s", "cpu_runs", "cpu_s",
                              "cpu_wall_s"}


def test_replica_group_inherits_the_spans():
    from repro.core.distributed import default_line_graph_mesh
    eng = build_engine(random_hypergraph(50, 70, seed=2), "hl-index")
    grp = ReplicaGroup(eng, 2, mesh=default_line_graph_mesh(),
                       config=ServiceConfig(max_batch=16), start=False)
    futs = grp.submit_many([MRRequest(i % 50, (7 * i) % 50)
                            for i in range(40)])
    grp.drain()
    [f.result(timeout=0) for f in futs]
    st = grp.stats()
    assert st.stages["refresh"].count == st.stages["dispatch"].count == 3
    assert st.queued == 40
