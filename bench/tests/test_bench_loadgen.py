"""The traffic generator: determinism by seed, latency from due time, and
the generator's own lateness."""
import sys
import threading
import time
from concurrent.futures import Future
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import loadgen  # noqa: E402

OPEN = {"loop": "open", "arrivals": {"process": "poisson",
                                     "rate_rps": 2000},
        "kinds": {"mr": 0.5, "s_reach": 0.5}, "s": [1, 4],
        "keys": {"dist": "uniform"}}
BIG_SEED = 2 ** 31 + 12345


class InstantService:
    """Answers every request at once, from the submitting thread."""

    def __init__(self):
        self.submitted = 0

    def submit(self, req, on_result=None):
        self.submitted += 1
        f = Future()
        f.set_result(0)
        if on_result is not None:
            on_result(req, f)
        return f


class DelayedService:
    """Answers each request ``delay`` seconds after it was submitted, from
    a thread of its own, and records how many were in flight."""

    def __init__(self, delay):
        self.delay = delay
        self.in_flight = 0
        self.most_in_flight = 0
        self.lock = threading.Lock()
        self.threads = []

    def submit(self, req, on_result=None):
        f = Future()
        with self.lock:
            self.in_flight += 1
            self.most_in_flight = max(self.most_in_flight, self.in_flight)

        def answer():
            time.sleep(self.delay)
            with self.lock:
                self.in_flight -= 1
            f.set_result(0)
            on_result(req, f)
        t = threading.Thread(target=answer, daemon=True)
        self.threads.append(t)
        t.start()
        return f


def make_request(is_mr, u, v, s):
    return (is_mr, u, v, s)


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_open_arrivals_are_fixed_by_the_seed(seed):
    t = loadgen.Traffic.from_dict(OPEN)
    a = loadgen.open_arrivals(t, seed, 2.0)
    b = loadgen.open_arrivals(t, seed, 2.0)
    assert np.array_equal(a, b)
    assert np.all(np.diff(a) >= 0) and a.max() < 2.0
    assert 3000 < a.size < 5000                      # 2,000/s for 2 s
    other = loadgen.open_arrivals(t, seed + 1, 2.0)
    assert not np.array_equal(a[:100], other[:100])
    # every seed: the same gaps, shuffled
    assert a.size == other.size
    assert np.allclose(np.sort(np.diff(a, prepend=0.0)),
                       np.sort(np.diff(other, prepend=0.0)))


@pytest.mark.parametrize("keys", [{"dist": "uniform"},
                                  {"dist": "zipf", "a": 1.1}])
def test_requests_are_fixed_by_the_seed_not_by_how_many_were_drawn(keys):
    t = loadgen.Traffic.from_dict(dict(OPEN, keys=keys))
    whole = loadgen.draw_requests(t, BIG_SEED, 500, 0, 70_000)
    part = loadgen.draw_requests(t, BIG_SEED, 500, 65_000, 2_000)
    for w, p in zip(whole, part):
        assert np.array_equal(w[65_000:67_000], p)
    is_mr, u, v, s = whole
    assert 0.45 < is_mr.mean() < 0.55
    assert u.min() >= 0 and u.max() < 500 and s.min() >= 1 and s.max() <= 4


def test_zipf_keys_are_skewed_and_uniform_keys_are_not():
    z = loadgen.Traffic.from_dict(dict(OPEN, keys={"dist": "zipf",
                                                   "a": 1.1}))
    u = loadgen.Traffic.from_dict(OPEN)
    top = lambda t: np.bincount(loadgen.draw_requests(
        t, 3, 1000, 0, 50_000)[1], minlength=1000).max() / 50_000
    assert top(z) > 0.05 > 0.005 > top(u)


def test_onoff_arrivals_are_silent_in_the_off_periods():
    t = loadgen.Traffic.from_dict(dict(OPEN, arrivals={
        "process": "onoff", "rate_rps": 4000, "on_s": 0.2, "off_s": 0.3}))
    a = loadgen.open_arrivals(t, 5, 2.0)
    phase = np.mod(a, 0.5)
    assert a.size > 1000 and np.all(phase < 0.2 + 1e-9)


def test_open_loop_latency_runs_from_the_due_time_and_lag_is_counted():
    """A generator held up for 30 ms charges the wait to every request
    that fell due meanwhile: its lag shows, and its latency (from due
    time, not from submit) includes it."""
    t = loadgen.Traffic.from_dict(OPEN)
    held = {"done": False}

    def slow_make_request(is_mr, u, v, s):
        if not held["done"]:
            held["done"] = True
            time.sleep(0.03)
        return make_request(is_mr, u, v, s)

    svc = InstantService()
    log = loadgen.drive(svc, t, 11, 100, 0.5,
                        make_request=slow_make_request)
    assert log.count == svc.submitted == loadgen.open_arrivals(
        t, 11, 0.5).size
    assert np.array_equal(log.due, log.t0 + loadgen.open_arrivals(t, 11,
                                                                   0.5))
    lag = log.lag_s()
    lat = log.latency_s()
    assert lag.max() > 0.02                    # the 30 ms hold shows
    assert np.all(lat >= lag - 1e-9)           # latency counts from due
    assert np.all(np.isfinite(log.done))
    # the instant service answers inside submit: each answer comes after
    # its submit, and the window counts exactly the answers inside it
    assert np.all(log.done >= log.submitted)
    end = log.t0 + 0.5
    assert log.completed_in_window() == np.count_nonzero(log.done <= end)
    assert (log.completed_in_window()
            <= np.count_nonzero(log.submitted <= end) <= log.count)


def test_the_generator_keeps_no_future_and_records_each_answer():
    """Futures are dropped as they resolve (so the window holds no pile of
    them for the garbage collector); what they said is kept as numbers:
    the value and type of an answer, a failure, or no answer at all."""
    import gc
    import weakref
    answers = [3, True, 2.0, ValueError("refused")]

    class Service:
        def __init__(self):
            self.refs, self.k = [], 0

        def submit(self, req, on_result=None):
            f = Future()
            self.refs.append(weakref.ref(f))
            a = answers[self.k % 4]
            self.k += 1
            if isinstance(a, Exception):
                f.set_exception(a)
            else:
                f.set_result(a)
            on_result(req, f)
            return f

    svc = Service()
    t = loadgen.Traffic.from_dict(OPEN)
    log = loadgen.drive(svc, t, 4, 100, 0.2, make_request=make_request)
    gc.collect()
    assert log.count == svc.k > 100
    assert all(r() is None for r in svc.refs)
    k = np.arange(log.count) % 4
    assert np.array_equal(log.kind, np.array(
        [loadgen.INT, loadgen.BOOL, loadgen.OTHER, loadgen.FAILED])[k])
    assert np.array_equal(log.value[k == 0], np.full((k == 0).sum(), 3))
    assert np.array_equal(log.value[k == 1], np.ones((k == 1).sum()))


def test_closed_loop_keeps_its_requests_in_flight_and_stops_at_the_end():
    t = loadgen.Traffic.from_dict({"loop": "closed", "outstanding": 8,
                                   "kinds": {"mr": 1.0}, "s": [1, 1],
                                   "keys": {"dist": "uniform"}})
    svc = DelayedService(0.002)
    log = loadgen.drive(svc, t, 3, 50, 0.3, make_request=make_request)
    for th in svc.threads:
        th.join(timeout=5)
    assert svc.most_in_flight <= 8
    in_window = log.due >= log.t0
    assert np.count_nonzero(in_window) > 8 * 10   # many 2 ms rounds of 8
    assert np.all(np.isfinite(log.done))
    # each request after the first eight was released by an answer
    assert np.all(np.isin(log.due[8:], log.done))
    assert np.all(log.submitted >= log.due - 1e-9)
    assert log.is_mr.all() and log.count == len(log.u)
    assert log.completed_in_window() == np.count_nonzero(
        (log.done >= log.t0) & (log.done <= log.t0 + 0.3))


@pytest.mark.parametrize("prime", [0, 8])
def test_closed_loop_opens_its_window_primed_at_a_pause(prime):
    """Answers that come in rounds 50 ms apart: the window opens at the
    first pause after ``prime`` answers are back, never at the first
    submit, so it starts with the loop full and no round half done."""
    t = loadgen.Traffic.from_dict({"loop": "closed", "outstanding": 8,
                                   "kinds": {"mr": 1.0}, "s": [1, 1],
                                   "keys": {"dist": "uniform"}})
    svc = DelayedService(0.05)
    log = loadgen.drive(svc, t, 3, 50, 0.3, make_request=make_request,
                        prime=prime)
    for th in svc.threads:
        th.join(timeout=5)
    assert np.all(log.submitted[:8] < log.t0)
    answered_before = np.count_nonzero(log.done < log.t0)
    assert answered_before == prime               # whole rounds only
    assert log.t0 - log.submitted[7] < 0.04 + 0.05 * (prime > 0)
    assert np.all(log.due[8 + prime:] >= log.t0)
    assert 24 <= log.completed_in_window() <= 56  # 8 per 50 ms round


def test_bad_traffic_is_refused():
    with pytest.raises(ValueError):
        loadgen.Traffic.from_dict(dict(OPEN, loop="sideways"))
    with pytest.raises(ValueError):
        loadgen.Traffic.from_dict(dict(OPEN, kinds={"witness": 1.0}))
    with pytest.raises(ValueError):
        loadgen.Traffic.from_dict({"loop": "closed", "kinds": {"mr": 1},
                                   "s": [1, 1], "keys": {"dist":
                                                         "uniform"}})
