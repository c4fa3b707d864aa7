"""The record one in-flight request lives in (``repro.serve.record``):
how few objects it holds, and that it keeps every contract of a
``concurrent.futures.Future`` on the service's own paths."""
import concurrent.futures as cf
import gc
import pathlib
import re
import sys
import threading
import time
import tracemalloc

import pytest

from repro.api import (DeadlineExceeded, MRRequest, ServiceConfig,
                       SReachRequest, build_engine, random_hypergraph, serve)
from repro.core import MSTOracle
from repro.serve.record import RequestFuture

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


@pytest.fixture(scope="module")
def engine():
    return build_engine(random_hypergraph(40, 60, seed=11), "hl-index")


@pytest.fixture(scope="module")
def oracle(engine):
    return MSTOracle(engine.h)


def _pairs(n, count):
    return [(i % n, (7 * i + 3) % n) for i in range(count)]


def test_in_flight_request_holds_at_most_three_tracked_objects(engine):
    svc = serve(engine, config=ServiceConfig(max_batch=4096), start=False)
    reqs = [MRRequest(u, v) for u, v in _pairs(engine.h.n, 1000)]
    futs = [None] * len(reqs)

    def on_result(request, future):
        pass

    gc.collect()
    was = gc.isenabled()
    gc.disable()
    try:
        before = len(gc.get_objects())
        for i, r in enumerate(reqs):
            futs[i] = svc.submit(r, on_result=on_result)
        added = len(gc.get_objects()) - before
    finally:
        if was:
            gc.enable()
    assert added <= 3 * len(reqs), added / len(reqs)
    assert all(isinstance(f, cf.Future) for f in futs)
    svc.drain()
    assert all(f.done() for f in futs)


def test_result_with_timeout_wakes_on_its_own_resolution(engine, oracle):
    # one request a take: the interactive one is answered first, and the
    # thread waiting on the other must sleep through it
    svc = serve(engine, config=ServiceConfig(max_batch=1, min_bucket=1),
                start=False)
    mine = svc.submit(MRRequest(1, 5))
    other = svc.submit(MRRequest(2, 6, priority="interactive"))
    got = []

    def wait():
        try:
            got.append(mine.result(timeout=30))
        except BaseException as exc:                   # noqa: BLE001
            got.append(exc)

    waiter = threading.Thread(target=wait)
    waiter.start()
    time.sleep(0.05)
    assert svc.drain(max_batches=1) == 1
    assert other.done() and not mine.done()
    time.sleep(0.05)
    assert waiter.is_alive() and got == []
    svc.drain()
    waiter.join(timeout=30)
    assert not waiter.is_alive()
    assert got == [oracle.mr(1, 5)]


def test_result_times_out_while_queued(engine):
    svc = serve(engine, start=False)
    fut = svc.submit(MRRequest(1, 2))
    with pytest.raises(TimeoutError):
        fut.result(timeout=0.01)
    with pytest.raises(TimeoutError):
        fut.exception(timeout=0)
    assert not fut.done() and not fut.running()
    svc.drain()
    assert fut.done() and fut.exception(timeout=0) is None


def test_cancel_while_queued_fires_on_result_and_is_skipped(engine, oracle):
    svc = serve(engine, start=False)
    seen = []
    on_result = lambda request, future: seen.append((request, future))
    keep = svc.submit(MRRequest(3, 4), on_result=on_result)
    req = SReachRequest(5, 6, 2)
    drop = svc.submit(req, on_result=on_result)
    assert drop.cancel() and drop.cancel()
    assert seen == [(req, drop)]
    assert drop.cancelled() and drop.done()
    with pytest.raises(cf.CancelledError):
        drop.result(timeout=0)
    assert svc.drain() == 2                  # both left the queue
    st = svc.stats()
    assert st.queued == st.answered == 1     # the cancelled one never ran
    assert drop.cancelled()
    assert keep.result(timeout=0) == oracle.mr(3, 4)
    assert not keep.cancel()                 # answered: no longer cancellable
    assert [f for _, f in seen] == [drop, keep]


def test_a_dispatch_error_reaches_every_record(engine):
    svc = serve(engine, start=False)
    boom = RuntimeError("device lost")

    def refresh():
        raise boom
    svc._refresh_snapshot = refresh
    failed = []
    futs = [svc.submit(MRRequest(u, v) if i % 2 else SReachRequest(u, v, 1),
                       on_result=lambda r, f: failed.append(f))
            for i, (u, v) in enumerate(_pairs(engine.h.n, 20))]
    svc.drain()
    assert len(failed) == 20
    for f in futs:
        assert f.exception(timeout=0) is boom
        with pytest.raises(RuntimeError, match="device lost"):
            f.result(timeout=0)


def test_add_done_callback_after_resolution_runs_at_once(engine):
    svc = serve(engine, start=False)
    fut = svc.submit(MRRequest(1, 3))
    early = []
    fut.add_done_callback(early.append)
    svc.drain()
    assert early == [fut]
    late = []
    fut.add_done_callback(late.append)
    assert late == [fut]


def test_wait_and_as_completed_over_service_futures(engine, oracle):
    pairs = _pairs(engine.h.n, 64)
    with serve(engine, config=ServiceConfig(max_batch=16,
                                            max_wait_ms=1.0)) as svc:
        futs = [svc.submit(MRRequest(u, v)) for u, v in pairs]
        done, not_done = cf.wait(futs, timeout=30)
        assert not not_done and done == set(futs)
        futs = [svc.submit(MRRequest(u, v)) for u, v in pairs]
        seen = list(cf.as_completed(futs, timeout=30))
        assert set(seen) == set(futs) and len(seen) == len(futs)
        first = cf.wait([svc.submit(MRRequest(1, 2))], timeout=30,
                        return_when=cf.FIRST_COMPLETED)
        assert len(first.done) == 1
    for (u, v), f in zip(pairs, futs):
        assert f.result(timeout=0) == oracle.mr(u, v)


def test_wait_sees_a_cancel_at_once(engine):
    svc = serve(engine, start=False)
    fut = svc.submit(MRRequest(1, 2))
    fut.cancel()
    done, _ = cf.wait([fut], timeout=5)
    assert done == {fut}
    assert list(cf.as_completed([fut], timeout=5)) == [fut]
    svc.drain()


def test_deadline_exceeded_arrives_through_the_record(engine):
    svc = serve(engine, start=False)
    seen = []
    req = MRRequest(0, 1, deadline_ms=0.01)
    fut = svc.submit(req, on_result=lambda r, f: seen.append(r))
    time.sleep(0.01)
    svc.drain()
    exc = fut.exception(timeout=0)
    assert isinstance(exc, DeadlineExceeded) and exc.request is req
    with pytest.raises(DeadlineExceeded):
        fut.result()
    assert seen == [req]
    assert svc.stats().expired == 1


def test_final_accessors_allocate_nothing(engine):
    svc = serve(engine, start=False)
    fut = svc.submit(MRRequest(1, 4))
    svc.drain()

    def peak_of(read):
        tracemalloc.start()
        try:
            base, _ = tracemalloc.get_traced_memory()
            tracemalloc.reset_peak()
            for _ in range(1000):
                read()
            _, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        return peak - base

    def accessors():
        fut.cancelled()
        fut.exception(timeout=0)
        fut.result()

    accessors()
    # what the loop itself allocates, then the same loop reading the record
    assert peak_of(accessors) == peak_of(lambda: None)


def test_record_is_a_future_the_stdlib_can_drive():
    rec = RequestFuture(MRRequest(1, 2), time.monotonic())
    assert isinstance(rec, cf.Future)
    assert rec.set_running_or_notify_cancel() and rec.running()
    rec.set_result(7)
    assert rec.result() == 7 and "returned int" in repr(rec)
    with pytest.raises(cf.InvalidStateError):
        rec.set_result(8)
    other = RequestFuture(MRRequest(1, 2), time.monotonic())
    assert other.cancel() and not other.set_running_or_notify_cancel()


def test_many_threads_submit_and_wait_under_fast_switching(engine, oracle):
    pairs = _pairs(engine.h.n, 200)
    want = [oracle.mr(u, v) for u, v in pairs]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    errors, called = [], [0]
    lock = threading.Lock()

    def on_result(request, future):
        with lock:
            called[0] += 1

    def client(k):
        try:
            futs = [svc.submit(MRRequest(u, v),
                               on_result=on_result if k % 2 else None)
                    for u, v in pairs]
            for f, w in zip(futs, want):
                if f.result(timeout=60) != w:
                    errors.append((k, f))
        except BaseException as exc:                   # noqa: BLE001
            errors.append(exc)

    try:
        with serve(engine, config=ServiceConfig(max_batch=64,
                                                max_wait_ms=0)) as svc:
            threads = [threading.Thread(target=client, args=(k,))
                       for k in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert errors == []
    assert called[0] == 8 * len(pairs)
    assert svc.stats().answered == 16 * len(pairs)


def test_gc_stage_counts_full_passes_while_serving(engine):
    svc = serve(engine)
    try:
        gc.collect()
        gc.collect()
        during = svc.stats().stages["gc"]
        assert during.count >= 2 and during.wall_s > 0
    finally:
        svc.close()
    assert svc._on_gc not in gc.callbacks
    gc.collect()
    assert svc.stats().stages["gc"].count == during.count


def test_gc_stage_absent_without_serving_thread(engine):
    svc = serve(engine, start=False)
    fut = svc.submit(MRRequest(1, 2))
    gc.collect()
    svc.drain()
    fut.result(timeout=0)
    svc.close()
    assert "gc" not in svc.stats().stages


def test_library_leaves_the_collector_settings_alone():
    banned = re.compile(r"\bgc\.(freeze|set_threshold|disable)\b")
    hits = [str(p) for p in SRC.rglob("*.py")
            if banned.search(p.read_text())]
    assert hits == []
