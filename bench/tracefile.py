"""From a profiler trace to the numbers the per-layer readers need.

Two steps, kept apart so the second can be tested on a recorded trace:

1. ``read_xplane(path)`` turns the ``.xplane.pb`` that ``jax.profiler``
   writes into plain events: for each chip's plane (``/device:TPU:<i>``)
   the lines ``XLA Ops`` (one event per operation the device ran) and
   ``XLA Modules`` (one event per program execution), and from the host
   planes the benchmark's own ``bench.*`` annotations.  Times are in
   nanoseconds on the trace's clock.
2. ``summarize(events, join_programs)`` reduces them inside the traced
   window (the ``bench.trace`` annotation): device busy time as the union
   of operation intervals, averaged over the devices; the device time of
   the join programs, matched by program name, and how many of their
   runs lie inside (a run across an edge counts by its share inside);
   the operations that took most time; and the longest idle gaps,
   each named by the ``bench.*`` annotation that covered most of it.

The peak table (``peaks.json``) is keyed by ``device_kind``; a device
missing from it is an error, never a default.
"""
from __future__ import annotations

import bisect
import dataclasses
import json
import re
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

__all__ = ["read_xplane", "summarize", "Summary", "peak", "UnknownDevice",
           "union_length", "WINDOW"]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.trace"
_PROGRAM_ID = re.compile(r"\(\d+\)$")
_LAYOUT = re.compile(r"\{[^}]*\}")
_CHIP = re.compile(r"/device:TPU:\d+$")     # not the CUSTOM planes
_PEAKS = Path(__file__).resolve().parent / "peaks.json"

Event = Tuple[str, float, float]          # name, start ns, duration ns


class UnknownDevice(KeyError):
    pass


def peak(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``."""
    table = json.loads(_PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device_kind {device_kind!r}; known: "
            f"{sorted(table)}")
    return table[device_kind]


def read_xplane(path) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in data.planes:
        if _CHIP.match(plane.name):
            lines = {ln.name: [(e.name, float(e.start_ns),
                                float(e.duration_ns)) for e in ln.events]
                     for ln in plane.lines
                     if ln.name in (OPS_LINE, MODULES_LINE)}
            devices.append({"plane": plane.name,
                            "ops": lines.get(OPS_LINE, []),
                            "modules": lines.get(MODULES_LINE, [])})
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in ln.events
                            if e.name.startswith("bench."))
    return {"devices": devices, "host": host}


def _clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def _merge(events: Sequence[Event]) -> List[Tuple[float, float]]:
    spans = sorted((s, s + d) for _, s, d in events)
    merged: List[List[float]] = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def union_length(events: Sequence[Event]) -> float:
    return sum(b - a for a, b in _merge(events))


def _program(name: str) -> str:
    return _PROGRAM_ID.sub("", name)


def _op(name: str) -> str:
    """``%fusion.21 = s32[16011264]{0:T(1024)} fusion(...)`` ->
    ``fusion.21 s32[16011264]``: the op and the shape it makes."""
    op, _, rest = name.partition(" = ")
    shape = ("(tuple)" if rest.startswith("(")
             else _LAYOUT.sub("", rest.split(" ", 1)[0]))
    return f"{op.lstrip('%')} {shape}".strip()


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    join_s: float
    join_runs: float          # runs, by the share of each inside
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _label(gap: Tuple[float, float], host: Sequence[Event]) -> str:
    best, best_overlap = "no bench annotation", 0.0
    for name, start, dur in host:
        overlap = min(gap[1], start + dur) - max(gap[0], start)
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def summarize(events: dict, join_programs: Sequence[str],
              top: int = 10) -> Summary:
    """Reduce ``read_xplane`` output inside the ``bench.trace`` window.
    ``join_programs`` are substrings of the program names (``XLA
    Modules`` events) that make up the join."""
    windows = [e for e in events["host"] if e[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} annotation, found "
                         f"{len(windows)}")
    _, lo, dur = windows[0]
    hi = lo + dur
    devices = events["devices"]
    if not devices:
        raise ValueError("the trace holds no device plane")
    host = [e for e in events["host"] if e[0] != WINDOW]
    busy = join = 0.0
    runs = 0.0
    per_op: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for dev in devices:
        ops = _clip(dev["ops"] or dev["modules"], lo, hi)
        busy += union_length(ops)
        mods = sorted(_clip(dev["modules"], lo, hi), key=lambda e: e[1])
        joined = [e for e in dev["modules"]
                  if any(p in e[0] for p in join_programs) and e[2] > 0]
        join += union_length(_clip(joined, lo, hi))
        # a run that straddles an edge of the window counts by the share
        # of it inside, as its time does
        runs += sum(max(min(a + d, hi) - max(a, lo), 0.0) / d
                    for _, a, d in joined)
        starts = [m[1] for m in mods]
        for name, start, d in ops:
            i = bisect.bisect_right(starts, start) - 1
            owner = (_program(mods[i][0]) if i >= 0
                     and start < mods[i][1] + mods[i][2] else "")
            key = f"{owner}/{_op(name)}" if owner else _op(name)
            per_op[key] = per_op.get(key, 0.0) + d
        cursor = lo
        for a, b in _merge(ops) + [(hi, hi)]:
            if a > cursor:
                gaps.append((cursor, a))
            cursor = max(cursor, b)
    k = len(devices)
    device_ops = sorted(((n, t * 1e-9 / k) for n, t in per_op.items()),
                        key=lambda x: -x[1])[:top]
    gaps.sort(key=lambda g: g[0] - g[1])
    idle_gaps = [(_label(g, host), (g[1] - g[0]) * 1e-9) for g in gaps[:top]]
    return Summary(window_s=dur * 1e-9, busy_s=busy * 1e-9 / k,
                   join_s=join * 1e-9 / k, join_runs=runs / k,
                   device_ops=device_ops, idle_gaps=idle_gaps)
