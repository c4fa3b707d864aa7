"""Compile time and compile count, from JAX's monitoring events.

The listener is copied from ``Clock`` in the repository's
``chip_smoke.py`` (its ``/jax/core/compile/backend_compile_duration``
and ``/jax/compilation_cache/cache_hits`` hooks); the benchmark keeps
its own copy so that the yardstick does not move with that script.
JAX records the backend-compile duration around compile-or-load, so a
program loaded from the persistent cache counts here too: once with the
time the load took.  Each event also keeps its end time, so the window
can count the compilations that fell inside it (there should be none).
"""
from __future__ import annotations

import time

__all__ = ["CompileClock"]

_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self):
        import jax
        self.events = []          # (end on perf_counter, seconds)
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == _COMPILE:
            self.events.append((time.perf_counter(), float(secs)))

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def seconds(self, start: float = float("-inf"),
                end: float = float("inf")) -> float:
        return sum(s for t, s in self.events if start <= t <= end)

    def count(self, start: float = float("-inf"),
              end: float = float("inf")) -> int:
        return sum(1 for t, _ in self.events if start <= t <= end)
