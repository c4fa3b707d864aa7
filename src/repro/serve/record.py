"""The record one in-flight request lives in: ``RequestFuture``.

``ReachabilityService.submit`` returns one object per request, and that
object is also what the scheduler queues and what dispatch resolves.  It
is a ``concurrent.futures.Future``, so ``result`` / ``exception`` /
``done`` / ``cancel`` / ``add_done_callback``, ``concurrent.futures.wait``
and ``as_completed`` work on it as on any future.

It is lean because of how long it lives.  Under saturation thousands of
requests are in flight for a good part of a second, long enough for the
garbage collector to promote everything a request holds into its oldest
generation; full collections then come in proportion to the number of
tracked objects each request holds.  A stock ``Future`` holds a
``Condition`` (with its ``RLock``, five bound lock methods and a waiter
``deque``) and two lists before anyone waits on it, and a done-callback
wrapping ``on_result`` costs a closure more.  This record is one tracked
object until someone waits on it:

* the request, its queue times and ``on_result`` are slots of the
  record; ``on_result(request, future)`` is called at resolution with
  no closure made per request;
* every record's state moves under one module lock, ``_LOCK``.  The
  condition a thread blocks on, the list of ``wait`` / ``as_completed``
  waiters and the list of extra callbacks are made on first use, under
  that lock, so a waiter can never miss a resolution.  Each record gets
  a condition of its own (over the shared lock), so resolving one
  record wakes only the threads that wait on that record;
* once a record is final its state never changes again, so the
  accessors read it without the lock and allocate nothing.

The service moves a record from pending to running when a take selects
it (``claim``), so a request can be cancelled only while it is queued;
a cancelled record is done at once (``wait`` and ``as_completed`` see
it), and dispatch skips it.  ``resolve`` and ``fail`` finish a group of
records under one acquisition of the lock and then run their callbacks.
"""
from __future__ import annotations

import threading
from concurrent.futures import CancelledError, Future, InvalidStateError
from concurrent.futures._base import (CANCELLED, CANCELLED_AND_NOTIFIED,
                                      FINISHED, LOGGER, PENDING, RUNNING)
from typing import Callable, Iterable, List, Optional, Sequence

__all__ = ["RequestFuture", "claim", "resolve", "fail"]

# Reentrant: ``concurrent.futures.wait`` acquires the ``_condition`` of
# every future it is given, and here those share this one lock.
_LOCK = threading.RLock()
_DONE = (CANCELLED, CANCELLED_AND_NOTIFIED, FINISHED)
_CANCELLED = (CANCELLED, CANCELLED_AND_NOTIFIED)


class RequestFuture(Future):
    """One submitted request: the future its caller holds and the entry
    the scheduler queues (``enqueued``: ``time.monotonic()`` at submit;
    ``expiry``: the absolute monotonic deadline, or None)."""

    __slots__ = ("request", "on_result", "enqueued", "expiry", "_state",
                 "_result", "_exception", "_cond", "_waiter_list",
                 "_callbacks")

    def __init__(self, request, enqueued: float,
                 expiry: Optional[float] = None,
                 on_result: Optional[Callable] = None):
        # Future.__init__ would build the condition and both lists now;
        # they are made on first use instead (the properties below)
        self.request = request
        self.on_result = on_result
        self.enqueued = enqueued
        self.expiry = expiry
        self._state = PENDING
        self._result = None
        self._exception = None
        self._cond = None
        self._waiter_list = None
        self._callbacks = None

    # -- what concurrent.futures.wait / as_completed reach for --------------

    @property
    def _condition(self) -> threading.Condition:
        cond = self._cond
        if cond is None:
            with _LOCK:
                cond = self._cond
                if cond is None:
                    cond = self._cond = threading.Condition(_LOCK)
        return cond

    @property
    def _waiters(self) -> list:
        waiters = self._waiter_list
        if waiters is None:
            with _LOCK:
                waiters = self._waiter_list
                if waiters is None:
                    waiters = self._waiter_list = []
        return waiters

    # -- the caller's side ---------------------------------------------------

    def cancel(self) -> bool:
        """Cancel the request if it is still queued; True if it is
        cancelled now or was before."""
        with _LOCK:
            state = self._state
            if state is not PENDING:
                return state in _CANCELLED
            self._state = CANCELLED_AND_NOTIFIED
            for waiter in self._waiter_list or ():
                waiter.add_cancelled(self)
            if self._cond is not None:
                self._cond.notify_all()
        self._invoke_callbacks()
        return True

    def cancelled(self) -> bool:
        return self._state in _CANCELLED

    def running(self) -> bool:
        return self._state is RUNNING

    def done(self) -> bool:
        return self._state in _DONE

    def result(self, timeout: Optional[float] = None):
        if self._state is not FINISHED:
            self._wait(timeout)
        exc = self._exception
        if exc is not None:
            try:
                raise exc
            finally:
                # the traceback holds this frame: no cycle through it
                self = exc = None
        return self._result

    def exception(self, timeout: Optional[float] = None):
        if self._state is not FINISHED:
            self._wait(timeout)
        return self._exception

    def add_done_callback(self, fn: Callable) -> None:
        with _LOCK:
            if self._state not in _DONE:
                if self._callbacks is None:
                    self._callbacks = []
                self._callbacks.append(fn)
                return
        try:
            fn(self)
        except Exception:                              # noqa: BLE001
            LOGGER.exception("exception calling callback for %r", self)

    def _wait(self, timeout: Optional[float]) -> None:
        """Block until the record is done or ``timeout`` passes; then
        raise ``CancelledError`` if it was cancelled and ``TimeoutError``
        if it is still not finished."""
        with _LOCK:
            if self._state not in _DONE:
                cond = self._condition
                if timeout is None:
                    while self._state not in _DONE:
                        cond.wait()
                else:
                    cond.wait_for(lambda: self._state in _DONE, timeout)
            state = self._state
        if state in _CANCELLED:
            raise CancelledError()
        if state is not FINISHED:
            raise TimeoutError()

    # -- the executor's side -------------------------------------------------

    def set_running_or_notify_cancel(self) -> bool:
        with _LOCK:
            if self._state is PENDING:
                self._state = RUNNING
                return True
            if self._state in _CANCELLED:
                return False
            raise RuntimeError(f"Future in unexpected state {self._state}")

    def set_result(self, result) -> None:
        self._set(result, None)

    def set_exception(self, exception) -> None:
        self._set(None, exception)

    def _set(self, result, exception) -> None:
        with _LOCK:
            if self._state in _DONE:
                raise InvalidStateError(f"{self._state}: {self!r}")
            self._finish(result, exception)
        self._invoke_callbacks()

    def _finish(self, result, exception) -> None:
        """Make the record final and wake its waiters; the caller holds
        ``_LOCK`` and runs the callbacks after releasing it."""
        self._result = result
        self._exception = exception
        self._state = FINISHED
        if self._waiter_list:
            for waiter in self._waiter_list:
                if exception is None:
                    waiter.add_result(self)
                else:
                    waiter.add_exception(self)
        if self._cond is not None:
            self._cond.notify_all()

    def _invoke_callbacks(self) -> None:
        on_result = self.on_result
        if on_result is not None:
            try:
                on_result(self.request, self)
            except Exception:                          # noqa: BLE001
                LOGGER.exception("exception calling on_result for %r", self)
        for fn in self._callbacks or ():
            try:
                fn(self)
            except Exception:                          # noqa: BLE001
                LOGGER.exception("exception calling callback for %r", self)

    def __repr__(self) -> str:
        state = self._state
        name = type(self).__name__
        if state is FINISHED:
            if self._exception is not None:
                return (f"<{name} at {id(self):#x} state=finished raised "
                        f"{type(self._exception).__name__}>")
            return (f"<{name} at {id(self):#x} state=finished returned "
                    f"{type(self._result).__name__}>")
        return f"<{name} at {id(self):#x} state={state.lower()}>"


def claim(records: Iterable[RequestFuture]) -> List[RequestFuture]:
    """The records of a take that are still pending, moved to running
    (no longer cancellable), in order; the cancelled ones are left out."""
    live = []
    with _LOCK:
        for rec in records:
            if rec._state is PENDING:
                rec._state = RUNNING
                live.append(rec)
    return live


def resolve(records: Sequence[RequestFuture], values: Iterable) -> None:
    """Finish each running record with its value, then run the callbacks
    of the records this call finished."""
    done = []
    with _LOCK:
        for rec, value in zip(records, values):
            if rec._state is RUNNING:
                if rec._cond is None and not rec._waiter_list:
                    rec._result = value          # no one waits: no wake-up
                    rec._state = FINISHED
                else:
                    rec._finish(value, None)
                done.append(rec)
    for rec in done:
        rec._invoke_callbacks()


def fail(records: Iterable[RequestFuture], exceptions: Iterable) -> None:
    """Finish each record that is not done yet with its exception (a
    cancelled or answered one keeps its outcome), then run the callbacks
    of the records this call finished."""
    done = []
    with _LOCK:
        for rec, exc in zip(records, exceptions):
            if rec._state not in _DONE:
                rec._finish(None, exc)
                done.append(rec)
    for rec in done:
        rec._invoke_callbacks()
