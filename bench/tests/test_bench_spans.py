"""The program's stage spans in a trace (``bench/spans.py``) and the
per-layer readers built on them: on a profile recorded on the CPU, on
synthetic events, and on the traces recorded on a TPU v5e chip; and the
guard that ``tracefile.summarize`` still reads the recorded traces as
before."""
import dataclasses
import gzip
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import harness  # noqa: E402
import manifest  # noqa: E402
import spans  # noqa: E402
import tracefile  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data"
NEW_READERS = ("queue_wait_ms_mean.open", "dispatch_host_ms_per_batch.open",
               "dispatch_host_ms_per_batch.closed",
               "join_wait_ms_per_batch.open", "idle_in_dispatch_share.open",
               "idle_in_dispatch_share.closed")


def _reader(name):
    return harness._load(manifest.reader_path(name), "t_spans_" + name)


def _span(name, start, end, **meta):
    return spans.Span(f"repro.serve.{name}", "python", float(start),
                      float(end - start), meta)


def _synthetic():
    """A 1,000 ns stretch, the device busy at 100-200 and 600-700; the
    service thread waits, takes one batch of 4 and dispatches it."""
    events = {"devices": [{"plane": "/device:TPU:0",
                           "ops": [("fusion", 100.0, 100.0),
                                   ("fusion", 600.0, 100.0)],
                           "modules": []}],
              "host": [(tracefile.WINDOW, 0.0, 1000.0),
                       ("bench.gen.submit", 0.0, 1000.0)]}
    sp = [_span("wait", 0, 100),
          _span("take", 200, 260, batch=1, taken=4, wait_s=0.004),
          _span("dispatch", 260, 900, batch=1),
          _span("refresh", 260, 280),
          _span("prepare", 280, 300, kind="mr", q=4, bucket=8),
          _span("join", 300, 700, bucket=8),
          _span("resolve", 700, 850, q=4)]
    return events, sp


def test_idle_under_dispatch_stages_counts_and_idle_under_wait_does_not():
    att = spans.attribute(*_synthetic())
    assert att.window_s == pytest.approx(1000e-9)
    assert att.idle_s == pytest.approx(800e-9)
    by = {k: round(v * 1e9) for k, v in att.idle_by_span.items()}
    assert by == {"repro.serve.wait": 100, "repro.serve.take": 60,
                  "repro.serve.refresh": 20, "repro.serve.prepare": 20,
                  "repro.serve.join": 300, "repro.serve.resolve": 150,
                  "repro.serve.dispatch.other": 50, "no repro span": 100}
    assert att.idle_waiting_s == pytest.approx(100e-9)
    assert att.idle_in_dispatch_s == pytest.approx(700e-9)
    # each gap is named by the service stage covering most of it
    assert [(n, round(g * 1e9)) for n, g in att.idle_gaps_by_span] == [
        ("repro.serve.join", 400), ("repro.serve.resolve", 300),
        ("repro.serve.wait", 100)]
    assert att.batches == 1 and att.taken == 4
    assert att.longest_spans[0][:2] == ("repro.serve.dispatch",
                                        pytest.approx(640e-9))


def test_a_gap_moved_from_resolve_to_wait_leaves_the_dispatch_share():
    events, sp = _synthetic()
    sp = [s for s in sp if s.name != "repro.serve.resolve"]
    sp.append(_span("wait", 700, 850))
    att = spans.attribute(events, sp)
    assert att.idle_in_dispatch_s == pytest.approx(550e-9)
    assert att.idle_waiting_s == pytest.approx(250e-9)


def test_readers_on_synthetic_spans(monkeypatch):
    att = spans.attribute(*_synthetic())
    monkeypatch.setattr(spans, "of_run", lambda run: att)
    run = SimpleNamespace(trace=object())
    read = {n: _reader(n).read(run) for n in NEW_READERS}
    assert read["queue_wait_ms_mean.open"] == pytest.approx(1.0)
    # take 60 + refresh 20 + prepare 20 + resolve 150 ns for one group
    assert read["dispatch_host_ms_per_batch.open"] == pytest.approx(2.5e-4)
    assert read["dispatch_host_ms_per_batch.closed"] == pytest.approx(2.5e-4)
    assert read["join_wait_ms_per_batch.open"] == pytest.approx(4e-4)
    assert read["idle_in_dispatch_share.open"] == pytest.approx(70.0)
    assert read["idle_in_dispatch_share.closed"] == pytest.approx(70.0)


def test_spans_across_the_stretch_edges_count_by_their_share_inside():
    """A closure join runs for seconds: one begun before the stretch
    and one still running at its end count by their share inside, and
    the host time per group stays the host stages' time."""
    events, _ = _synthetic()
    sp = [_span("join", -300, 100, bucket=8),
          _span("resolve", 100, 150, q=4),
          _span("take", 150, 160, batch=2, taken=4, wait_s=0.008),
          _span("prepare", 160, 200, kind="mr", q=4, bucket=8),
          _span("join", 200, 1200, bucket=8)]
    att = spans.attribute(events, sp)
    assert att.count("repro.serve.join") == pytest.approx(0.25 + 0.8)
    assert att.wall_s("repro.serve.join") == pytest.approx(900e-9)
    assert att.taken == 4 and att.queue_wait_s == pytest.approx(0.008)
    host = sum(att.wall_s(n) for n in spans.HOST_STAGES)
    assert host == pytest.approx(100e-9)


def test_spans_open_at_the_stretch_edges_are_inferred_for_idle_time():
    """A closure's dispatch lasts seconds, so the profiler holds
    neither the dispatch and join running when the stretch opens nor
    those still running when it closes; the loop's order names the idle
    time under them all the same."""
    events = {"devices": [{"plane": "/device:TPU:0",
                           "ops": [("fusion", 0.0, 80.0),
                                   ("fusion", 160.0, 430.0),
                                   ("fusion", 660.0, 340.0)],
                           "modules": []}],
              "host": [(tracefile.WINDOW, 0.0, 1000.0)]}
    sp = [_span("resolve", 100, 120, q=4),
          _span("linger", 130, 131),
          _span("take", 131, 140, batch=2, taken=8, wait_s=0.1),
          _span("refresh", 141, 142),
          _span("prepare", 142, 150, kind="mr", q=4, bucket=8),
          _span("join", 150, 600, bucket=8),
          _span("resolve", 600, 620, q=4),
          _span("prepare", 620, 630, kind="s_reach", q=4, bucket=8)]
    att = spans.attribute(events, sp)
    by = {k: round(v * 1e9) for k, v in att.idle_by_span.items()}
    assert by == {"repro.serve.join": 70, "repro.serve.resolve": 40,
                  "repro.serve.dispatch.other": 11,
                  "repro.serve.linger": 1, "repro.serve.take": 9,
                  "repro.serve.refresh": 1, "repro.serve.prepare": 18,
                  "no repro span": 0}
    assert att.idle_gaps_by_span == [("repro.serve.join", 80e-9),
                                     ("repro.serve.join", 70e-9)]
    # only the recorded join counts toward the time per join
    assert att.count("repro.serve.join") == 1


def test_dispatch_other_is_what_no_child_covers():
    sp = [_span("dispatch", 0, 100), _span("prepare", 10, 20),
          _span("join", 20, 50), _span("resolve", 60, 90),
          _span("dispatch", 200, 300), _span("join", 200, 300)]
    cover = spans._cover(sp, 0.0, 1000.0)
    assert cover["repro.serve.dispatch.other"] == [(0, 10), (50, 60),
                                                   (90, 100)]


def test_stalls_are_named_by_the_span_that_covers_them():
    events, sp = _synthetic()
    att = spans.attribute(events, sp)
    # the stretch began 2 s into the window: a stall 2 s + 300 ns in,
    # lasting 200 ns, lies under the join; one before the stretch is left
    stalls = [[2.0 + 300e-9, 200e-9], [0.5, 0.1]]
    named = spans.name_stalls(att, events, stalls, t0=10.0, t_trace=12.0)
    assert [s[2] for s in named] == ["repro.serve.join"]


def test_no_service_spans_means_no_attribution_and_no_metric(monkeypatch):
    """The parent program, or a trace of anything else: the readers
    report nothing and raise nothing."""
    path = RECORDED / "trace_v5e_closure_join.xplane.pb"
    assert spans.read_spans(path) == []
    monkeypatch.setattr(spans, "traced_xplane", lambda *a: path)
    run = SimpleNamespace(trace=object())
    assert spans.of_run(run) is None
    for name in NEW_READERS:
        assert _reader(name).read(run) is None
        assert _reader(name).read(SimpleNamespace(trace=None)) is None


def test_service_spans_in_a_cpu_profile(tmp_path):
    """A running service traced by ``jax.profiler`` on the CPU: the
    stage spans appear with their metadata, and a take and its dispatch
    share one batch number."""
    import jax
    from repro.api import (MRRequest, ServiceConfig, SReachRequest,
                           random_hypergraph, serve)
    h = random_hypergraph(60, 90, seed=1)
    svc = serve(h, "hl-index", config=ServiceConfig(max_batch=32))
    warm = [svc.mr(0, 1), svc.s_reach(0, 1, 2)]
    [f.result(timeout=60) for f in warm]
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(tracefile.WINDOW):
            futs = [svc.submit(MRRequest(i % 60, (7 * i) % 60) if i % 2
                               else SReachRequest(i % 60, (3 * i) % 60, 2))
                    for i in range(100)]
            [f.result(timeout=60) for f in futs]
    finally:
        jax.profiler.stop_trace()
        svc.close()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    sp = spans.read_spans(path)
    names = {s.name for s in sp}
    assert {"repro.serve.take", "repro.serve.dispatch", "repro.serve.refresh",
            "repro.serve.prepare", "repro.serve.join",
            "repro.serve.resolve"} <= names
    takes = {s.meta["batch"]: s for s in sp if s.name == "repro.serve.take"}
    dispatches = [s for s in sp if s.name == "repro.serve.dispatch"]
    assert dispatches
    for d in dispatches:
        t = takes[d.meta["batch"]]
        assert t.meta["taken"] >= 1 and t.end <= d.start
    # every request was taken once (the last dispatch may still be open
    # when the trace stops: its futures resolve before it ends)
    assert sum(t.meta["taken"] for t in takes.values()) == 100
    assert {s.meta["kind"] for s in sp
            if s.name == "repro.serve.prepare"} == {"mr", "s_reach"}
    assert all(s.start >= 0 and s.dur >= 0 for s in sp)


def test_summarize_reads_the_recorded_traces_as_before():
    """``tracefile.summarize`` is the yardstick of the accepted metrics:
    on both recorded traces it returns exactly what it returned before
    the service had spans (``tracefile_summaries.json``)."""
    want = json.loads((RECORDED / "tracefile_summaries.json").read_text())
    with gzip.open(RECORDED / "trace_v5e_walmart_open.json.gz", "rt") as f:
        events = json.load(f)
    got = {"trace_v5e_walmart_open.json.gz": events,
           "trace_v5e_closure_join.xplane.pb": tracefile.read_xplane(
               RECORDED / "trace_v5e_closure_join.xplane.pb")}
    for name, ev in got.items():
        s = dataclasses.asdict(tracefile.summarize(ev,
                                                   harness.JOIN_PROGRAMS))
        assert json.loads(json.dumps(s)) == want[name], name


def test_companion_command_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/spans.py", "--workload",
         "walmart.point.open", "--seed", "2147483653", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


WALMART_SPANS = RECORDED / "trace_v5e_walmart_spans.xplane.pb"


def test_recorded_walmart_spans_tie_takes_to_dispatches():
    """250 ms of ``walmart.point.open`` on a TPU v5e (588-wide labels,
    5,200 req/s): a piece of a traced run's stretch, cut to the lines the
    benchmark reads (the chip's ops and programs, the host's ``bench.*``
    and ``repro.*`` events) and given its own ``bench.trace``."""
    sp = spans.read_spans(WALMART_SPANS)
    takes = {s.meta["batch"]: s for s in sp if s.name == "repro.serve.take"}
    dispatches = [s for s in sp if s.name == "repro.serve.dispatch"]
    assert len(dispatches) >= 10
    for d in dispatches:
        if d.meta["batch"] in takes:
            assert takes[d.meta["batch"]].end <= d.start
    kinds = [s.meta["kind"] for s in sp if s.name == "repro.serve.prepare"]
    assert set(kinds) == {"mr", "s_reach"}


def test_recorded_walmart_idle_splits_into_waiting_and_dispatch(monkeypatch):
    events, sp = spans.load(WALMART_SPANS)
    att = spans.attribute(events, sp)
    summary = tracefile.summarize(events, harness.JOIN_PROGRAMS)
    assert att.window_s == pytest.approx(summary.window_s)
    assert att.idle_s / att.window_s == pytest.approx(summary.idle_share)
    # every idle nanosecond is under one stage, or under none
    assert sum(att.idle_by_span.values()) == pytest.approx(att.idle_s)
    assert att.idle_by_span["no repro span"] < 0.05 * att.idle_s
    # the device waits most while the thread is inside the join: the
    # launch before the device runs and the fetch after it
    assert max(att.idle_by_span, key=att.idle_by_span.get) == \
        "repro.serve.join"
    assert all(n.startswith("repro.serve.") for n, _ in
               att.idle_gaps_by_span)
    monkeypatch.setattr(spans, "traced_xplane", lambda *a: WALMART_SPANS)
    run = SimpleNamespace(trace=summary)
    read = {n: _reader(n).read(run) for n in NEW_READERS}
    waiting = 100.0 * att.idle_waiting_s / att.window_s
    assert read["idle_in_dispatch_share.open"] + waiting == pytest.approx(
        100.0 * summary.idle_share)
    assert 0 < read["idle_in_dispatch_share.open"] < 100
    # the thread waits for each join longer than the device runs it
    device_ms = _reader("join_ms_per_batch.open").read(run)
    assert read["join_wait_ms_per_batch.open"] > device_ms > 0
    assert 0 < read["dispatch_host_ms_per_batch.open"] < \
        read["join_wait_ms_per_batch.open"]
    assert 0 < read["queue_wait_ms_mean.open"] < 100


def test_stage_totals_over_a_window_take_the_cpu_share_of_its_runs():
    """The companion line's stage totals: differences of two readings,
    and the CPU share of the runs between them that read the clock (not
    of the runs before, such as a warm-up that compiled)."""
    from repro.stages import StageTotal
    before = {"join": StageTotal(2, 1.0, 2, 0.9, 1.0)}
    after = {"join": StageTotal(6, 9.0, 4, 1.1, 5.0),
             "take": StageTotal(3, 0.3, 1, 0.0, 0.1)}
    d = spans._stage_delta(before, after)
    assert d["join"]["count"] == 4 and d["join"]["cpu_runs"] == 2
    assert d["join"]["wall_s"] == pytest.approx(8.0)
    assert d["join"]["cpu_share"] == pytest.approx(0.2 / 4.0)
    assert d["take"]["cpu_share"] == 0.0
