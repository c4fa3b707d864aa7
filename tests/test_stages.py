"""Stage spans (``repro.stages``) and the construction stages the
engines record in ``build_stages``."""
import os
import sys
import threading
import time

import pytest

from repro import stages
from repro.api import build_engine, random_hypergraph
from repro.stages import Stages, StageTotal


def test_span_counts_runs_and_times_wall_above_cpu():
    st = Stages("repro.test")
    for _ in range(3):
        with st.span("sleep"):
            time.sleep(0.01)
    t = st.totals()["sleep"]
    assert t.count == 3
    assert t.wall_s >= 0.03
    # a sleeping thread is not on the CPU
    assert t.cpu_runs == 3 and t.cpu_wall_s == t.wall_s
    assert 0 <= t.cpu_share < 0.5


def test_busy_span_cpu_time_tracks_its_wall_time(monkeypatch):
    # the span reads fake clocks: a 50 ms spin in 1 ms steps, off the
    # CPU for every fifth step as a loaded machine would take it off,
    # and each clock read costs both clocks 1 us
    clock = {"wall": 0, "cpu": 0}

    def read(name):
        clock["wall"] += 1_000
        clock["cpu"] += 1_000
        return clock[name]

    monkeypatch.setattr(stages, "_wall_ns", lambda: read("wall"))
    monkeypatch.setattr(stages, "_cpu_ns", lambda: read("cpu"))
    st = Stages("repro.test")
    with st.span("spin"):
        for step in range(50):
            clock["wall"] += 1_000_000
            if step % 5:
                clock["cpu"] += 1_000_000
    t = st.totals()["spin"]
    assert t.wall_s >= 0.05
    assert t.cpu_s <= t.cpu_wall_s + 1e-3
    assert t.cpu_share > 0.5


def test_nested_spans_each_count_and_the_outer_holds_the_inner():
    st = Stages("repro.test")
    with st.span("outer", batch=7) as outer:
        for _ in range(2):
            with st.span("inner", q=3):
                time.sleep(0.005)
        outer.set(taken=2)
    tot = st.totals()
    assert tot["outer"].count == 1 and tot["inner"].count == 2
    assert tot["outer"].wall_s >= tot["inner"].wall_s >= 0.01


def test_span_counts_even_when_the_stage_raises():
    st = Stages("repro.test")
    with pytest.raises(ValueError):
        with st.span("fails"):
            raise ValueError("boom")
    assert st.totals()["fails"].count == 1


def test_add_accumulates_caller_measured_time_and_totals_is_a_copy():
    st = Stages("repro.test")
    st.add("x", 2_000_000_000, 1_000_000_000)
    st.add("x", 1_000_000_000, 0, count=4)
    snap = st.totals()
    assert snap["x"] == StageTotal(count=5, wall_s=3.0, cpu_runs=5,
                                   cpu_s=1.0, cpu_wall_s=3.0)
    st.add("x", 1, 1)
    assert snap["x"].count == 5 and st.totals()["x"].count == 6


def test_add_from_inside_the_lock_on_a_new_thread_does_not_deadlock():
    # a gc.callbacks hook can run between any two bytecodes, so ``add``
    # may come while this same thread holds the lock (in ``totals``)
    st = Stages("t")
    st.add("before", 1)

    def nested():
        with st._lock:
            st.totals()
            st.add("gc", 5_000_000, 4_000_000)

    t = threading.Thread(target=nested, daemon=True)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    assert st.totals()["gc"] == StageTotal(1, 0.005, 1, 0.004, 0.005)


def test_cpu_clock_is_read_on_every_32nd_run_or_on_long_stages():
    st = Stages("repro.test")
    for _ in range(100):
        with st.span("s"):
            pass
    t = st.totals()["s"]
    # runs 0, 32, 64 and 96 read it
    assert t.count == 100 and t.cpu_runs == 4
    # the runs that read it were half on the CPU: so is the stage
    st.add("w", 3_000, None, count=3)
    st.add("w", 1_000, 500)
    w = st.totals()["w"]
    assert (w.count, w.cpu_runs) == (4, 1)
    assert w.wall_s == pytest.approx(4_000e-9)
    assert w.cpu_share == pytest.approx(0.5)
    assert st.reads_cpu("new") and not st.reads_cpu("w")
    # runs of 10 ms or more read it every time
    st.add("long", 30_000_000, None, count=3)
    assert st.reads_cpu("long")


def test_spans_from_more_threads_than_cores_lose_no_count():
    """Each thread adds to totals of its own; with the interpreter
    switching threads as often as it can, no run is lost, even while
    another thread reads the totals."""
    st = Stages("repro.test")
    workers = 2 * (os.cpu_count() or 2)

    def work():
        for _ in range(300):
            with st.span("t"):
                pass
            st.add("u", 1, 1)

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(workers)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 60
        while (any(t.is_alive() for t in threads)
               and time.monotonic() < deadline):
            st.totals()
            time.sleep(0.001)
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(before)
    assert not any(t.is_alive() for t in threads)
    tot = st.totals()
    assert tot["t"].count == tot["u"].count == 300 * workers
    assert tot["u"].wall_s == pytest.approx(300 * workers * 1e-9)


def test_hlindex_build_records_its_stages():
    h = random_hypergraph(120, 180, seed=3)
    eng = build_engine(h, "hl-index")
    tot = eng.build_stages.totals()
    assert {"build", "build.labels", "build.neighbors", "build.finish",
            "build.minimize"} <= set(tot)
    assert all(tot[k].count == 1 for k in ("build", "build.labels",
                                            "build.finish",
                                            "build.minimize"))
    # one neighbor-index initialization per hyperedge the build visits
    assert tot["build.neighbors"].count == eng.idx.stats["neighbor_inits"]
    assert (tot["build.labels"].wall_s + tot["build.minimize"].wall_s
            <= tot["build"].wall_s)
    assert (tot["build.neighbors"].wall_s + tot["build.finish"].wall_s
            <= tot["build.labels"].wall_s)


def test_closure_build_records_its_stages():
    h = random_hypergraph(40, 60, seed=4)
    eng = build_engine(h, "closure")
    tot = eng.build_stages.totals()
    assert set(tot) == {"build", "build.line_graph", "build.rows"}
    assert all(tot[k].count == 1 for k in tot)
    assert sum(tot[k].wall_s for k in ("build.line_graph", "build.rows")
               ) <= tot["build"].wall_s
    assert 1 <= eng.rounds <= h.m


def test_engines_without_stages_expose_empty_totals():
    h = random_hypergraph(30, 40, seed=5)
    assert build_engine(h, "online").build_stages.totals() == {}
