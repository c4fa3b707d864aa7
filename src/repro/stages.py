"""Stage spans: per-stage counts and times, and profiler annotations.

A ``Stages`` object keeps, for each stage name, how many times the stage
ran, its wall time (``time.perf_counter_ns``) and the CPU time of the
thread that ran it (``time.thread_time_ns``).  Wall minus CPU time of a
host stage is the time its thread was runnable but did not run, or was
blocked (the interpreter lock, a device fetch, a lock).

    stages = Stages("repro.serve")
    with stages.span("prepare", kind="mr", q=37):
        ...
    stages.totals()["prepare"]   # StageTotal(count=1, wall_s=..., cpu_s=...)

The thread CPU clock is a system call, slow where system calls are (5 us
a read on a TPU v5e host), so a span reads it on the first run of its
stage in each thread, on every ``CPU_EVERY``-th run after, and on every
run of a stage whose runs average ``CPU_ALWAYS_NS`` or more, where two
reads cost under a thousandth.  ``cpu_s`` and ``cpu_wall_s`` sum the
CPU and wall time of the runs that read it; their ratio is the stage's
CPU share.  The wall clock is read on every run.

Each span also enters ``jax.profiler.TraceAnnotation("<prefix>.<stage>",
**meta)``, so that a profiler trace shows it on the host thread that ran
it, on the same clock as the device's operations.  With no profiler
running no annotation is built; the counters are always kept.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Stages", "StageTotal"]

_wall_ns = time.perf_counter_ns
_cpu_ns = time.thread_time_ns
CPU_EVERY = 32
CPU_ALWAYS_NS = 10_000_000


@dataclasses.dataclass(frozen=True)
class StageTotal:
    """What one stage added up to: runs and wall seconds; and of the
    ``cpu_runs`` runs that read the thread CPU clock, their CPU and wall
    seconds."""

    count: int
    wall_s: float
    cpu_runs: int
    cpu_s: float
    cpu_wall_s: float

    @property
    def cpu_share(self) -> float:
        """Share of the stage's wall time its thread was on the CPU."""
        return self.cpu_s / self.cpu_wall_s if self.cpu_wall_s else 0.0


class _Span:
    __slots__ = ("_mine", "_stage", "_annotation", "_wall", "_cpu")

    def __init__(self, stages: "Stages", stage: str, meta: dict):
        self._mine = stages._mine()
        self._stage = stage
        # built only while a profiler records: an idle annotation still
        # costs its construction
        self._annotation = (TraceAnnotation(f"{stages.prefix}.{stage}",
                                            **meta)
                            if TraceAnnotation.is_enabled() else None)

    def __enter__(self) -> "_Span":
        if self._annotation is not None:
            self._annotation.__enter__()
        self._cpu = _cpu_ns() if _reads_cpu(self._mine, self._stage) \
            else None
        self._wall = _wall_ns()
        return self

    def set(self, **meta) -> None:
        """Add metadata known only once the stage has run (a profiler
        trace shows it on the span)."""
        if self._annotation is not None:
            self._annotation.set_metadata(**meta)

    def __exit__(self, *exc) -> None:
        wall = _wall_ns() - self._wall
        cpu = None if self._cpu is None else _cpu_ns() - self._cpu
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        _add(self._mine, self._stage, wall, cpu, 1)


def _reads_cpu(mine: Dict[str, List[int]], stage: str) -> bool:
    t = mine.get(stage)
    return (t is None or t[0] % CPU_EVERY == 0
            or t[1] >= CPU_ALWAYS_NS * t[0])


def _add(mine: Dict[str, List[int]], stage: str, wall_ns: int,
         cpu_ns: Optional[int], count: int) -> None:
    t = mine.get(stage)
    if t is None:
        # runs, wall; and of the runs that read the CPU clock: their
        # number, CPU and wall
        t = mine[stage] = [0, 0, 0, 0, 0]
    t[0] += count
    t[1] += wall_ns
    if cpu_ns is not None:
        t[2] += count
        t[3] += cpu_ns
        t[4] += wall_ns


class Stages:
    """Per-stage totals under one annotation prefix.  Each thread adds
    to totals of its own, so a span takes no lock; ``totals()`` sums
    them."""

    def __init__(self, prefix: str):
        self.prefix = prefix
        self._local = threading.local()
        self._threads: List[Dict[str, List[int]]] = []
        self._lock = threading.Lock()

    def _mine(self) -> Dict[str, List[int]]:
        try:
            return self._local.totals
        except AttributeError:
            mine = self._local.totals = {}
            with self._lock:
                self._threads.append(mine)
            return mine

    def span(self, stage: str, **meta) -> _Span:
        """A context manager timing one run of ``stage``; ``meta`` goes
        on its profiler annotation."""
        return _Span(self, stage, meta)

    def reads_cpu(self, stage: str) -> bool:
        """Whether this thread's next run of ``stage`` should read the
        CPU clock (see the module's docstring)."""
        return _reads_cpu(self._mine(), stage)

    def add(self, stage: str, wall_ns: int, cpu_ns: Optional[int] = None,
            count: int = 1) -> None:
        """Add time measured by the caller (a stage too short or too
        frequent to annotate one run at a time); ``cpu_ns`` None when the
        CPU clock was not read."""
        _add(self._mine(), stage, wall_ns, cpu_ns, count)

    def totals(self) -> Dict[str, StageTotal]:
        """The totals so far, summed over threads, by stage name."""
        with self._lock:
            threads = list(self._threads)
        summed: Dict[str, List[int]] = {}
        for mine in threads:
            for stage, t in mine.copy().items():
                acc = summed.setdefault(stage, [0, 0, 0, 0, 0])
                for i, x in enumerate(tuple(t)):
                    acc[i] += x
        return {k: StageTotal(n, w * 1e-9, r, c * 1e-9, cw * 1e-9)
                for k, (n, w, r, c, cw) in summed.items()}
