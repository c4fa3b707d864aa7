"""The program's own stage spans in a profiler trace, beside the device.

The service annotates each stage of its admission loop as
``repro.serve.<stage>`` and its index build as ``repro.build`` and
``repro.build.<stage>`` (``src/repro/stages.py``); the profiler writes
those host events and the device's operations into one ``.xplane.pb``,
on one clock.  This module reduces them inside the traced stretch (the
``bench.trace`` annotation), beside ``tracefile.summarize``, which it
leaves as it is:

* ``read_spans(path)``: every host event named ``repro.*``, with the
  thread that ran it and its metadata;
* ``attribute(events, spans)``: the device's idle time split by the
  service-thread stage that covers it (``wait`` and ``linger`` mean no
  work was queued; every other stage is host time in dispatch), the
  longest idle gaps named by that stage, the longest spans, and per-stage
  counts and wall time inside the stretch (a span across an edge by its
  share inside);
* ``python3 bench/spans.py --workload <cell> --seed <n> --seconds <s>``
  runs the cell once as ``run.py --trace 1`` does and prints its result
  line, with the cell's end-to-end metrics beside the per-layer ones;
  then one more JSON line: the attribution, each host stall of the
  window named by the span that covers it, and the service's and the
  build's stage totals (count, wall and thread-CPU seconds) over the
  window, read from ``ServiceStats.stages`` and ``engine.build_stages``.

A trace of a program without these spans yields no attribution (None),
never an error.
"""
from __future__ import annotations

import bisect
import dataclasses
import json
import os
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import tracefile

__all__ = ["Span", "Attribution", "read_spans", "load", "traced_xplane",
           "of_run", "attribute", "name_stalls", "WAITING", "DISPATCH",
           "HOST_STAGES"]

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SERVE = "repro.serve."
WAITING = ("repro.serve.wait", "repro.serve.linger")
DISPATCH = "repro.serve.dispatch"
DISPATCH_CHILDREN = ("repro.serve.refresh", "repro.serve.prepare",
                     "repro.serve.join", "repro.serve.resolve")
# the service thread's host work per batch around the device call
HOST_STAGES = ("repro.serve.take", "repro.serve.refresh",
               "repro.serve.prepare", "repro.serve.resolve")
JOIN = "repro.serve.join"
TAKE = "repro.serve.take"
PREPARE = "repro.serve.prepare"
RESOLVE = "repro.serve.resolve"
# the loop's stages outside any dispatch
LOOP = ("repro.serve.wait", "repro.serve.linger", TAKE)
# the part of a dispatch under none of its children
DISPATCH_OTHER = "repro.serve.dispatch.other"

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    thread: str
    start: float          # ns, on the trace's clock
    dur: float            # ns
    meta: dict

    @property
    def end(self) -> float:
        return self.start + self.dur


def read_spans(path) -> List[Span]:
    """Every host event named ``repro.*`` in the ``.xplane.pb`` at
    ``path``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith("repro."):
                    out.append(Span(e.name, ln.name, float(e.start_ns),
                                    float(e.duration_ns), dict(e.stats)))
    out.sort(key=lambda s: s.start)
    return out


_LOADED: Dict[tuple, tuple] = {}


def load(path) -> Tuple[dict, List[Span]]:
    """``tracefile.read_xplane(path)`` and ``read_spans(path)``, read
    once per file (every reader of a run asks for the same trace)."""
    key = (str(path), os.stat(path).st_mtime_ns)
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = (tracefile.read_xplane(path), read_spans(path))
    return _LOADED[key]


def traced_xplane() -> Optional[Path]:
    """The profile that ``run.py --trace 1`` wrote into
    ``<checkout>/.bench_out`` (``harness.run`` clears the directory
    before it traces), or None."""
    found = sorted((ROOT / ".bench_out" / "trace").glob(
        "plugins/profile/*/*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


def of_run(run) -> Optional["Attribution"]:
    """The attribution of a ``--trace 1`` run's profile, for the
    per-layer readers; None for an untraced run or a program without
    service spans."""
    path = traced_xplane() if run.trace is not None else None
    if path is None:
        return None
    key = ("attribution", str(path), os.stat(path).st_mtime_ns)
    if key not in _LOADED:
        _LOADED[key] = attribute(*load(path))
    return _LOADED[key]


def _merge(intervals) -> List[Interval]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _intersect(xs: Sequence[Interval],
               ys: Sequence[Interval]) -> List[Interval]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(xs: Sequence[Interval]) -> float:
    return sum(b - a for a, b in xs)


def _overlap(a: float, b: float, xs: Sequence[Interval],
             starts: Sequence[float]) -> float:
    """How much of ``[a, b)`` the sorted disjoint ``xs`` cover."""
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    got = 0.0
    while i < len(xs) and xs[i][0] < b:
        got += max(min(b, xs[i][1]) - max(a, xs[i][0]), 0.0)
        i += 1
    return got


@dataclasses.dataclass
class Attribution:
    window_s: float
    idle_s: float                 # device idle, averaged over the chips
    idle_by_span: Dict[str, float]   # that idle time by covering stage
    idle_gaps_by_span: List[Tuple[str, float]]
    longest_spans: List[Tuple[str, float, dict]]
    stages: Dict[str, Dict[str, float]]   # clipped to the stretch
    taken: float
    queue_wait_s: float
    _cover: Dict[str, Tuple[List[Interval], List[float]]] = \
        dataclasses.field(default=None, repr=False)

    @property
    def idle_waiting_s(self) -> float:
        return sum(self.idle_by_span.get(n, 0.0) for n in WAITING)

    @property
    def idle_in_dispatch_s(self) -> float:
        return self.idle_s - self.idle_waiting_s

    @property
    def batches(self) -> float:
        """Kind groups dispatched: ``join`` spans, one device call each
        (what ``ServiceStats.batches`` counts), by their share inside."""
        return self.count(JOIN)

    def wall_s(self, name: str) -> float:
        return self.stages.get(name, {}).get("wall_s", 0.0)

    def count(self, name: str) -> float:
        return self.stages.get(name, {}).get("count", 0.0)

    def label(self, a: float, b: float) -> str:
        """The stage that covers most of ``[a, b)`` (ns)."""
        best, most = "no repro span", 0.0
        for name, (xs, starts) in self._cover.items():
            got = _overlap(a, b, xs, starts)
            if got > most:
                best, most = name, got
        return best


def _window(events: dict) -> Interval:
    wins = [e for e in events["host"] if e[0] == tracefile.WINDOW]
    if len(wins) != 1:
        raise ValueError(f"expected one {tracefile.WINDOW!r} annotation, "
                         f"found {len(wins)}")
    _, lo, dur = wins[0]
    return lo, lo + dur


def _cover(spans: Sequence[Span], lo: float,
           hi: float) -> Dict[str, List[Interval]]:
    """Each service stage's clipped, merged intervals: the leaves, and
    the part of each dispatch under none of its children."""
    by_name: Dict[str, List[Interval]] = {}
    for s in spans:
        if s.name.startswith(SERVE) and s.end > lo and s.start < hi:
            by_name.setdefault(s.name, []).append((max(s.start, lo),
                                                   min(s.end, hi)))
    cover = {n: _merge(xs) for n, xs in by_name.items() if n != DISPATCH}
    if DISPATCH in by_name:
        inside = _merge(x for n in DISPATCH_CHILDREN
                        for x in cover.get(n, []))
        own, j = [], 0
        for a, b in _merge(by_name[DISPATCH]):
            cursor = a
            while j < len(inside) and inside[j][1] <= a:
                j += 1
            k = j
            while k < len(inside) and inside[k][0] < b:
                if inside[k][0] > cursor:
                    own.append((cursor, inside[k][0]))
                cursor = max(cursor, inside[k][1])
                k += 1
            if b > cursor:
                own.append((cursor, b))
        cover[DISPATCH_OTHER] = own
    return cover


def _dropped(spans: Sequence[Span], lo: float, hi: float) -> List[Span]:
    """The service spans the trace cannot hold, inferred from the loop's
    fixed order (take, then a dispatch: refresh, and per kind group
    prepare, join, resolve; then linger or wait, and the next take).
    The profiler records no span that is open when it starts or stops,
    and a closure's dispatch or join lasts seconds.  So: a ``resolve``
    that is the stretch's first group stage follows a join begun before
    it; a ``prepare`` that is the last span precedes a join still
    running; group stages before the first take, linger or wait belong
    to a dispatch begun before the stretch, and a take with nothing
    after it but group stages is followed by a dispatch that outlasts
    it.  These only name idle time; no count or wall time includes
    them."""
    serve = sorted((s for s in spans if s.name.startswith(SERVE)
                    and s.end > lo and s.start < hi), key=lambda s: s.start)
    if not serve:
        return []
    thread = serve[0].thread
    out = []
    groups = [s for s in serve if s.name in DISPATCH_CHILDREN]
    if groups and groups[0].name == RESOLVE:
        out.append(Span(JOIN, thread, lo, groups[0].start - lo, {}))
    last = max(serve, key=lambda s: s.end)
    if last.name == PREPARE:
        out.append(Span(JOIN, thread, last.end, hi - last.end, {}))
    dispatches = [s for s in serve if s.name == DISPATCH]
    tops = [s for s in serve if s.name in LOOP]
    first_top = tops[0].start if tops else hi

    def enclosed(g):
        return any(d.start <= g.start and g.end <= d.end for d in dispatches)

    if any(g.start < first_top and not enclosed(g) for g in groups):
        out.append(Span(DISPATCH, thread, lo, first_top - lo, {}))
    if tops and tops[-1].name == TAKE and tops[-1].meta.get("taken", 0) \
            and not any(d.start >= tops[-1].end for d in dispatches):
        out.append(Span(DISPATCH, thread, tops[-1].end,
                        hi - tops[-1].end, {}))
    return out


def attribute(events: dict, spans: Sequence[Span],
              top: int = 10) -> Optional[Attribution]:
    """Reduce ``read_xplane`` events and ``read_spans`` spans inside the
    ``bench.trace`` stretch; None when the trace holds no service span
    (a program that does not annotate its stages)."""
    if not any(s.name.startswith(SERVE) for s in spans):
        return None
    lo, hi = _window(events)
    devices = events["devices"]
    if not devices:
        raise ValueError("the trace holds no device plane")
    cover = _cover(list(spans) + _dropped(spans, lo, hi), lo, hi)
    idle_by: Dict[str, float] = {}
    idle = 0.0
    gaps: List[Interval] = []
    for dev in devices:
        busy = _merge((a, a + d) for _, a, d in
                      tracefile._clip(dev["ops"] or dev["modules"], lo, hi))
        cursor, dev_gaps = lo, []
        for a, b in busy + [(hi, hi)]:
            if a > cursor:
                dev_gaps.append((cursor, a))
            cursor = max(cursor, b)
        idle += _length(dev_gaps)
        gaps.extend(dev_gaps)
        for name, xs in cover.items():
            idle_by[name] = idle_by.get(name, 0.0) + _length(
                _intersect(dev_gaps, xs))
    k = len(devices)
    idle_by = {n: t * 1e-9 / k for n, t in idle_by.items()}
    idle_s = idle * 1e-9 / k
    idle_by["no repro span"] = max(idle_s - sum(idle_by.values()), 0.0)

    # a span across an edge of the stretch counts by its share inside,
    # as its time does (a closure join runs for seconds)
    stages: Dict[str, Dict[str, float]] = {}
    taken, waited = 0.0, 0.0
    for s in spans:
        inside = min(s.end, hi) - max(s.start, lo)
        if inside < 0 or (inside == 0 and s.dur > 0) or s.start >= hi:
            continue
        share = inside / s.dur if s.dur > 0 else 1.0
        st = stages.setdefault(s.name, {"count": 0.0, "wall_s": 0.0})
        st["count"] += share
        st["wall_s"] += inside * 1e-9
        if s.name == TAKE:
            taken += share * s.meta.get("taken", 0)
            waited += share * s.meta.get("wait_s", 0.0)
    inside = [s for s in spans if s.end > lo and s.start < hi]
    longest = sorted(inside, key=lambda s: -s.dur)[:top]
    att = Attribution(
        window_s=(hi - lo) * 1e-9, idle_s=idle_s, idle_by_span=idle_by,
        idle_gaps_by_span=[], stages=stages, taken=taken,
        queue_wait_s=waited,
        longest_spans=[(s.name, s.dur * 1e-9, s.meta) for s in longest],
        _cover={n: (xs, [a for a, _ in xs]) for n, xs in cover.items()})
    gaps.sort(key=lambda g: g[0] - g[1])
    att.idle_gaps_by_span = [(att.label(a, b), (b - a) * 1e-9)
                             for a, b in gaps[:top]]
    return att


def name_stalls(att: Attribution, events: dict, stalls, t0: float,
                t_trace: float) -> List[list]:
    """Each host stall ``[seconds into the window, seconds]`` (the
    harness's ``host_stalls``) that falls in the traced stretch, as
    ``[into window, seconds, stage]``: the stage covering most of it.
    ``t0`` is the window's start and ``t_trace`` the moment the stretch
    began, both on ``time.perf_counter``; the trace's clock is tied to it
    at the stretch's start."""
    lo, hi = _window(events)
    out = []
    for into, length in stalls:
        a = lo + (t0 + into - t_trace) * 1e9
        b = a + length * 1e9
        if b > lo and a < hi:
            out.append([into, length, att.label(a, b)])
    return out


def _as_json(att: Attribution) -> dict:
    return {
        "window_s": att.window_s, "idle_s": att.idle_s,
        "idle_waiting_s": att.idle_waiting_s,
        "idle_in_dispatch_s": att.idle_in_dispatch_s,
        "idle_by_span": dict(sorted(att.idle_by_span.items(),
                                    key=lambda kv: -kv[1])),
        "idle_gaps_by_span": [list(g) for g in att.idle_gaps_by_span],
        "longest_spans": [list(s) for s in att.longest_spans],
        "stages": att.stages, "batches": att.batches, "taken": att.taken,
        "queue_wait_s": att.queue_wait_s}


def _stage_delta(before: dict, after: dict) -> Dict[str, dict]:
    """Stage totals between two readings, with the CPU share of the
    runs between them that read the CPU clock."""
    out = {}
    for name, t in sorted(after.items()):
        d = {k: getattr(t, k) - (getattr(before[name], k)
                                 if name in before else 0)
             for k in ("count", "wall_s", "cpu_runs", "cpu_s",
                       "cpu_wall_s")}
        d["cpu_share"] = (d["cpu_s"] / d["cpu_wall_s"] if d["cpu_wall_s"]
                          else None)
        out[name] = d
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    # as run.py does before JAX is first imported
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(BENCH))
    import harness
    import jax
    import manifest
    import run as bench_run

    seen: dict = {"stats": []}
    start_serving, watch_start = harness.start_serving, \
        harness._HostWatch.start
    start_trace, emit = jax.profiler.start_trace, harness.emit

    def capture_service(*a, **kw):
        svc, graph, parts = start_serving(*a, **kw)
        stats = svc.stats

        def recorded():
            s = stats()
            seen["stats"].append(s)
            return s
        svc.stats = recorded
        build = getattr(svc.engine, "build_stages", None)
        if build is not None:
            seen["build_stages"] = build.totals()
        return svc, graph, parts

    def capture_t0(watch, t0):
        seen["t0"] = t0
        return watch_start(watch, t0)

    def capture_trace_start(*a, **kw):
        out = start_trace(*a, **kw)
        seen["t_trace"] = time.perf_counter()
        return out

    def capture_result(result):
        seen["result"] = result
        emit(result)

    # a traced run reads the end-to-end metrics too, so that what the
    # trace costs shows beside an untraced run
    cell_of = manifest.cell

    def with_end_to_end(*a, **kw):
        c = cell_of(*a, **kw)
        c.per_layer = c.per_layer + c.end_to_end
        return c

    manifest.cell = with_end_to_end
    harness.start_serving = capture_service
    harness._HostWatch.start = capture_t0
    jax.profiler.start_trace = capture_trace_start
    harness.emit = capture_result
    rc = bench_run.main(["--workload", args.workload, "--seed",
                         str(args.seed), "--seconds", str(args.seconds),
                         "--trace", "1"])
    if rc:
        return rc
    path = traced_xplane()
    events, spans = load(path)
    att = attribute(events, spans)
    line: dict = {"spans": None if att is None else _as_json(att)}
    if att is not None:
        line["stalls_by_span"] = name_stalls(
            att, events, seen["result"]["window"]["host_stalls"],
            seen["t0"], seen["t_trace"])
    stats = seen["stats"]
    if len(stats) >= 2 and hasattr(stats[0], "stages"):
        s0, s1 = stats[0], stats[-1]
        line["service_stages"] = _stage_delta(s0.stages, s1.stages)
        queued = s1.queued - s0.queued
        line["queue_wait_ms_mean"] = ((s1.queue_wait_s - s0.queue_wait_s)
                                      / queued * 1e3 if queued else None)
        line["batches"] = s1.batches - s0.batches
    if "build_stages" in seen:
        line["build_stages"] = _stage_delta({}, seen["build_stages"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
