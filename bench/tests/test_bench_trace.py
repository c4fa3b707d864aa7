"""The reduction from a profiler trace to per-layer numbers, on synthetic
events and on a trace recorded on a TPU v5e chip, and the peak table."""
import gzip
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import manifest  # noqa: E402
import tracefile  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data"


def _events(ops, modules, host, window=(0.0, 1000.0)):
    return {"devices": [{"plane": "/device:TPU:0", "ops": ops,
                         "modules": modules}],
            "host": [(tracefile.WINDOW, window[0], window[1] - window[0])]
            + host}


def test_union_counts_overlaps_once():
    ev = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 31, 1)]
    assert tracefile.union_length(ev) == 15 + 5


def test_summary_on_synthetic_events():
    ops = [("fusion.1", 100, 50), ("fusion.2", 140, 60),    # 100..200
           ("copy", 600, 100),                               # 600..700
           ("outside", 2000, 50)]                            # not traced
    modules = [("jit_batched_mr(17)", 90, 120),
               ("jit_other(3)", 590, 120)]
    host = [("bench.gen.sleep", 200, 400), ("bench.gen.submit", 700, 300)]
    s = tracefile.summarize(_events(ops, modules, host),
                            harness.JOIN_PROGRAMS)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(200e-9)
    assert s.idle_share == pytest.approx(0.8)
    assert s.join_runs == 1
    assert s.join_s == pytest.approx(120e-9)
    assert dict(s.device_ops)["jit_batched_mr/fusion.2"] == \
        pytest.approx(60e-9)
    assert dict(s.device_ops)["jit_other/copy"] == pytest.approx(100e-9)
    # idle gaps 0..100, 200..600, 700..1000, longest first, each named
    # by the annotation that covered most of it
    assert [round(g * 1e9) for _, g in s.idle_gaps] == [400, 300, 100]
    assert [n for n, _ in s.idle_gaps] == [
        "bench.gen.sleep", "bench.gen.submit", "no bench annotation"]


def test_join_runs_across_the_window_edges_count_by_their_share_inside():
    """Three 200 ns join runs: one across each edge of the window, a
    quarter and a half of it inside, and one whole: 1.75 runs in 350 ns,
    so the time per run reads the runs' own 200 ns, not 350 / 3."""
    modules = [("jit_batched_mr(1)", -150, 200),          # 50 inside
               ("jit_batched_mr(1)", 400, 200),           # whole
               ("jit_batched_mr(1)", 900, 200)]           # 100 inside
    ops = [("fusion", a, d) for _, a, d in modules]
    s = tracefile.summarize(_events(ops, modules, []),
                            harness.JOIN_PROGRAMS)
    assert s.join_runs == pytest.approx(1.75)
    assert s.join_s == pytest.approx(350e-9)
    per_run = _reader("join_ms_per_batch.closed").read(
        SimpleNamespace(trace=s))
    assert per_run == pytest.approx(350e-9 / 1.75 * 1e3)


def test_summary_needs_one_window_and_a_device():
    with pytest.raises(ValueError):
        tracefile.summarize({"devices": [], "host": [
            (tracefile.WINDOW, 0, 10)]}, harness.JOIN_PROGRAMS)
    with pytest.raises(ValueError):
        tracefile.summarize({"devices": [{"ops": [], "modules": []}],
                             "host": []}, harness.JOIN_PROGRAMS)


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_raise():
    p = tracefile.peak("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(tracefile.UnknownDevice):
        tracefile.peak("TPU v99 imaginary")


def _reader(name):
    return harness._load(manifest.reader_path(name), "t_" + name)


def test_roofline_counts_needed_bytes_over_join_time():
    """4,096 requests of two 7,818-wide rows each must read 512 MB; at
    819 GB/s that is 0.625 ms, so a 62.5 ms join reads 1%."""
    trace = tracefile.Summary(window_s=1.0, busy_s=0.5, join_s=0.125,
                              join_runs=2, device_ops=[], idle_gaps=[])
    run = SimpleNamespace(trace=trace, answered=8192, batches=2,
                          join_bytes_per_request=8 * 2 * 7818,
                          device_kind="TPU v5 lite")
    share = _reader("join_roofline_share.closed").read(run)
    assert share == pytest.approx(100 * 4096 * 8 * 2 * 7818 / 819e9
                                  / 0.0625)
    run.device_kind = "TPU v99 imaginary"
    with pytest.raises(tracefile.UnknownDevice):
        _reader("join_roofline_share.closed").read(run)
    run.trace = None
    assert _reader("join_roofline_share.closed").read(run) is None


def test_summary_of_a_recorded_open_loop_trace():
    """250 ms of walmart.point.open on a TPU v5e (14-wide labels): many
    short join runs, the device idle nearly all the time, idle gaps laid
    at the generator's door."""
    with gzip.open(RECORDED / "trace_v5e_walmart_open.json.gz", "rt") as f:
        events = json.load(f)
    s = tracefile.summarize(events, harness.JOIN_PROGRAMS)
    assert s.window_s == pytest.approx(0.25)
    assert s.join_runs == 90
    assert 0 < s.busy_s < 0.05 * s.window_s
    # every op ran inside a join program; the programs' spans also hold
    # the device's gaps between their ops
    assert s.busy_s <= s.join_s < 1.01 * s.busy_s
    assert 0.95 < s.idle_share < 1
    assert len(s.device_ops) == len(s.idle_gaps) == 10
    assert all(n.startswith("jit_batched_mr/") for n, _ in s.device_ops)
    assert {n for n, _ in s.idle_gaps} <= {"bench.gen.sleep",
                                           "bench.gen.submit",
                                           "no bench annotation"}
    assert s.idle_gaps == sorted(s.idle_gaps, key=lambda g: -g[1])


def test_read_xplane_of_a_recorded_closure_join():
    """A raw profiler file from a TPU v5e: one 2,048-row join over
    7,818-wide closure rows.  Only the chip's plane counts as a device
    (not the empty ``/device:CUSTOM`` plane beside it)."""
    events = tracefile.read_xplane(RECORDED /
                                   "trace_v5e_closure_join.xplane.pb")
    assert [d["plane"] for d in events["devices"]] == ["/device:TPU:0"]
    assert [h[0] for h in events["host"]] == [tracefile.WINDOW]
    s = tracefile.summarize(events, harness.JOIN_PROGRAMS)
    assert s.join_runs == 1
    assert s.busy_s == pytest.approx(1.8773, rel=1e-3)
    assert s.join_s == pytest.approx(s.busy_s, rel=1e-6)
    assert s.device_ops[0][0] == "jit_batched_mr/fusion.21 s32[16011264]"
