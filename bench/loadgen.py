"""The one traffic generator: reads a traffic file and drives a service.

A traffic file (``bench/traffic/<name>.json``) is parameters only:

    {"loop": "open",
     "arrivals": {"process": "poisson", "rate_rps": 9000},
     "kinds": {"mr": 0.5, "s_reach": 0.5},
     "s": [1, 4],
     "keys": {"dist": "uniform"}}

* ``loop``: ``open`` sends each request at its due time whatever the
  service does (independent users); ``closed`` keeps ``outstanding``
  requests in flight, and each answer releases the next (callers that
  wait for their reply).  A closed loop is primed before its window
  opens: every request is in flight and the answers have paused, so
  the window starts with a full queue and a batch on the device.
* ``arrivals`` (open loop): ``poisson`` at ``rate_rps``, or ``onoff``:
  Poisson at ``rate_rps`` during ``on_s`` seconds, then silence for
  ``off_s`` seconds, repeated.
* ``kinds``: the share of MR and s-reach requests.
* ``s``: s-reach thresholds, uniform on the closed range.
* ``keys``: ``uniform`` vertices, or ``zipf`` with exponent ``a`` over a
  seeded permutation of the vertices (so the hot vertices differ by
  seed, their number does not).

Everything is drawn from the seed alone: the same seed gives the same
requests in the same order and, in an open loop, the same due times.
Every seed gets the same arrival gaps, shuffled (``open_arrivals``).  A
closed loop draws its requests in fixed chunks, so how many the window
consumes depends on the service, but the i-th request does not.

Latency is taken from a request's due time to the moment its future
resolves, so a generator that runs late charges its lateness to the
requests it delayed; the lateness itself is kept as ``lag``.  The
``bench.*`` trace annotations name what the generator thread is doing,
so idle gaps on the device can be laid at the host's door.

The generator keeps no future: each answer is copied into arrays as its
future resolves.  Hundreds of thousands of live futures would make each
full pass of Python's garbage collector take about a second, a stall
of the benchmark's own making inside the window.
"""
from __future__ import annotations

import dataclasses
import queue
import time
from functools import partial
from typing import Callable, Optional

import numpy as np
from jax.profiler import TraceAnnotation

__all__ = ["Traffic", "Log", "drive", "open_arrivals", "draw_requests",
           "NONE", "INT", "BOOL", "OTHER", "FAILED"]

_CHUNK = 1 << 16
_GAPS_STREAM = 0         # the one stream the arrival gaps are drawn from
# what a request's future resolved to (``Log.kind``); NONE: never resolved
NONE, INT, BOOL, OTHER, FAILED = range(5)
_QUIET_S = 0.002         # a closed loop is primed once answers pause this long
_PRIME_CAP_S = 1.0       # ... or this long after it is primed at most


@dataclasses.dataclass(frozen=True)
class Traffic:
    loop: str
    kinds: dict
    s: tuple
    keys: dict
    arrivals: Optional[dict] = None
    outstanding: int = 0

    @classmethod
    def from_dict(cls, d: dict) -> "Traffic":
        t = cls(loop=d["loop"], kinds=dict(d["kinds"]), s=tuple(d["s"]),
                keys=dict(d["keys"]), arrivals=d.get("arrivals"),
                outstanding=int(d.get("outstanding", 0)))
        if t.loop == "open":
            if t.arrivals is None or t.arrivals.get("process") not in (
                    "poisson", "onoff"):
                raise ValueError(f"open loop needs arrivals poisson or "
                                 f"onoff; got {t.arrivals!r}")
        elif t.loop == "closed":
            if t.outstanding < 1:
                raise ValueError("closed loop needs outstanding >= 1")
        else:
            raise ValueError(f"unknown loop {t.loop!r}")
        if set(t.kinds) - {"mr", "s_reach"} or not t.kinds:
            raise ValueError(f"kinds must be among mr, s_reach; got "
                             f"{sorted(t.kinds)}")
        if t.keys.get("dist") not in ("uniform", "zipf"):
            raise ValueError(f"unknown key distribution {t.keys!r}")
        return t


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


def open_arrivals(traffic: Traffic, seed: int, seconds: float) -> np.ndarray:
    """Due times (seconds from the window's start) of every request of an
    open loop, ascending, all below ``seconds``.

    The gaps between arrivals are drawn once, from a stream that no seed
    changes, and the seed only shuffles them: every seed offers the same
    number of requests with the same gaps, in another order."""
    arr = traffic.arrivals
    rate = float(arr["rate_rps"])
    rng = _rng(_GAPS_STREAM, 1)
    gaps = []
    total = 0.0
    while total < seconds:
        g = rng.exponential(1.0 / rate, _CHUNK)
        gaps.append(g)
        total += float(g.sum())
    g = np.concatenate(gaps)
    g = g[:int(np.count_nonzero(np.cumsum(g) < seconds))]
    t = np.cumsum(_rng(seed, 1).permutation(g))
    if arr["process"] == "onoff":
        on, off = float(arr["on_s"]), float(arr["off_s"])
        # t counts seconds of "on" time; stretch it over on/off periods
        t = t + np.floor(t / on) * off
    return t[t < seconds]


def draw_requests(traffic: Traffic, seed: int, n: int, start: int,
                  count: int):
    """Requests ``start .. start + count`` of the seed's fixed sequence:
    ``(is_mr, u, v, s)`` arrays.  Drawn in chunks of 65,536, each from
    its own stream, so a request does not depend on how many came
    before it was asked for."""
    out = []
    first = start // _CHUNK
    last = (start + count - 1) // _CHUNK if count else first - 1
    for c in range(first, last + 1):
        out.append(_chunk(traffic, seed, n, c))
    if not out:
        e = np.empty(0, np.int64)
        return np.empty(0, bool), e, e, e
    cat = [np.concatenate([o[i] for o in out]) for i in range(4)]
    lo = start - first * _CHUNK
    return tuple(a[lo:lo + count] for a in cat)


def _chunk(traffic: Traffic, seed: int, n: int, c: int):
    rng = _rng(seed, 2, c)
    share_mr = float(traffic.kinds.get("mr", 0.0)) / float(
        sum(traffic.kinds.values()))
    is_mr = rng.random(_CHUNK) < share_mr
    lo, hi = traffic.s
    s = rng.integers(lo, hi + 1, _CHUNK)
    if traffic.keys["dist"] == "uniform":
        u = rng.integers(0, n, _CHUNK)
        v = rng.integers(0, n, _CHUNK)
    else:
        a = float(traffic.keys["a"])
        weights = 1.0 / np.arange(1, n + 1) ** a
        cdf = np.cumsum(weights / weights.sum())
        perm = _rng(seed, 3).permutation(n)
        pick = lambda: perm[np.minimum(np.searchsorted(cdf, rng.random(
            _CHUNK)), n - 1)]
        u, v = pick(), pick()
    return is_mr, u.astype(np.int64), v.astype(np.int64), s.astype(np.int64)


@dataclasses.dataclass
class Log:
    """What the window did, per request, on the host clock (seconds on
    ``time.perf_counter``)."""

    t0: float                  # window start
    seconds: float             # window length
    is_mr: np.ndarray
    u: np.ndarray
    v: np.ndarray
    s: np.ndarray
    due: np.ndarray            # absolute due time
    submitted: np.ndarray      # absolute time submit() was called
    done: np.ndarray           # absolute time the future resolved
    kind: np.ndarray           # what it resolved to: NONE, INT, BOOL, ...
    value: np.ndarray          # the answer as a number (INT, BOOL)

    @property
    def count(self) -> int:
        return len(self.due)

    def latency_s(self) -> np.ndarray:
        return self.done - self.due

    def lag_s(self) -> np.ndarray:
        return self.submitted - self.due

    def completed_in_window(self) -> int:
        end = self.t0 + self.seconds
        return int(np.count_nonzero((self.done >= self.t0)
                                    & (self.done <= end)))


def drive(svc, traffic: Traffic, seed: int, n: int, seconds: float, *,
          make_request: Callable, on_start: Callable = None,
          prime: int = 0, drain_timeout_s: float = 60.0) -> Log:
    """Run one window of ``traffic`` against ``svc`` (already started).

    ``make_request(is_mr, u, v, s)`` builds one request object.
    ``on_start(t0)`` is called as the window opens.  A closed loop
    opens its window only after ``prime`` answers have come back (one
    full batch, say) and the answers then pause.  After the window the
    call waits up to ``drain_timeout_s`` for every answer still owed; a
    request that gets none keeps ``done`` as NaN."""
    if traffic.loop == "open":
        return _drive_open(svc, traffic, seed, n, seconds, make_request,
                           on_start, drain_timeout_s)
    return _drive_closed(svc, traffic, seed, n, seconds, make_request,
                         on_start, prime, drain_timeout_s)


def _drive_open(svc, traffic, seed, n, seconds, make_request, on_start,
                drain_timeout_s) -> Log:
    offsets = open_arrivals(traffic, seed, seconds)
    count = offsets.size
    is_mr, u, v, s = draw_requests(traffic, seed, n, 0, count)
    submitted = np.full(count, np.nan)
    done = np.full(count, np.nan)
    kind = np.full(count, NONE, np.int8)
    value = np.full(count, -1, np.int64)

    def mark(k, _req, fut, _clock=time.perf_counter):
        t = _clock()
        kind[k], value[k] = _read(fut)
        done[k] = t

    clock = time.perf_counter
    t0 = clock()
    if on_start is not None:
        on_start(t0)
    due = t0 + offsets
    i = 0
    while i < count:
        now = clock()
        if due[i] > now:
            with TraceAnnotation("bench.gen.sleep"):
                time.sleep(due[i] - now)
            continue
        j = int(np.searchsorted(due, now, side="right"))
        with TraceAnnotation("bench.gen.submit"):
            for k in range(i, j):
                req = make_request(bool(is_mr[k]), int(u[k]), int(v[k]),
                                   int(s[k]))
                submitted[k] = clock()
                svc.submit(req, on_result=partial(mark, k))
        i = j
    deadline = t0 + seconds + drain_timeout_s
    while np.isnan(done).any() and clock() < deadline:
        time.sleep(0.01)
    return Log(t0, seconds, is_mr, u, v, s, due, submitted, done, kind,
               value)


def _drive_closed(svc, traffic, seed, n, seconds, make_request, on_start,
                  prime, drain_timeout_s) -> Log:
    answered: "queue.SimpleQueue[int]" = queue.SimpleQueue()
    cols = {k: [] for k in ("is_mr", "u", "v", "s")}
    due, submitted, done, kind, value = [], [], [], [], []
    clock = time.perf_counter
    state = {"drawn": 0}

    def mark(k, _req, fut):
        t = clock()
        kind[k], value[k] = _read(fut)
        done[k] = t
        answered.put(k)

    def send(release_t):
        k = len(due)
        if k >= state["drawn"]:
            for name, a in zip(("is_mr", "u", "v", "s"), draw_requests(
                    traffic, seed, n, k, _CHUNK)):
                cols[name].append(a)
            state["drawn"] += _CHUNK
        c, r = divmod(k, _CHUNK)
        req = make_request(bool(cols["is_mr"][c][r]), int(cols["u"][c][r]),
                           int(cols["v"][c][r]), int(cols["s"][c][r]))
        due.append(release_t)
        done.append(np.nan)
        kind.append(NONE)
        value.append(-1)
        submitted.append(clock())
        svc.submit(req, on_result=partial(mark, k))

    # prime the loop before the window opens: put every request in
    # flight, release on each answer until ``prime`` answers are back
    # (the first, uneven batches taken while the loop was filling are
    # done), then open at the next pause of _QUIET_S in the answers (a
    # batch is on the device and the queue is full), or _PRIME_CAP_S
    # after the ``prime``-th answer at the latest
    with TraceAnnotation("bench.client.submit"):
        for _ in range(traffic.outstanding):
            send(clock())
    in_flight = traffic.outstanding
    end = float("inf")

    def release(k):
        t = done[k]
        if t < end:
            with TraceAnnotation("bench.client.submit"):
                send(t)
            return 1
        return 0

    give_up = clock() + drain_timeout_s
    back, cap = 0, (clock() + _PRIME_CAP_S if prime <= 0 else None)
    while cap is None or clock() < cap:
        try:
            k = answered.get(timeout=_QUIET_S if cap is not None
                             else max(give_up - clock(), 0.001))
        except queue.Empty:
            break
        in_flight += release(k) - 1
        back += 1
        if cap is None and back >= prime:
            cap = clock() + _PRIME_CAP_S
    t0 = clock()
    if on_start is not None:
        on_start(t0)
    end = t0 + seconds
    deadline = end + drain_timeout_s
    while in_flight:
        with TraceAnnotation("bench.client.wait"):
            try:
                k = answered.get(timeout=max(deadline - clock(), 0.001))
            except queue.Empty:
                break
        in_flight += release(k) - 1
    count = len(due)
    flat = [np.concatenate(cols[name])[:count] for name in
            ("is_mr", "u", "v", "s")]
    done = np.asarray(done, float)          # read before kind and value
    return Log(t0, seconds, *flat, np.asarray(due), np.asarray(submitted),
               done, np.asarray(kind, np.int8), np.asarray(value, np.int64))


def _read(fut):
    """``(kind, value)`` of a resolved future: an ``int`` or a ``bool``
    answer with its value, another type, or a failure."""
    if fut.cancelled() or fut.exception(timeout=0) is not None:
        return FAILED, -1
    a = fut.result()
    if type(a) is bool:
        return BOOL, int(a)
    if type(a) is int:
        return INT, a
    return OTHER, -1
