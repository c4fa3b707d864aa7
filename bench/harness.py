"""One run of one cell: set-up, the measured window, the check, the metrics.

Set-up (``setup_s``, from process start to the window's start): the
hypergraph is drawn from the seed (``hypergraphs.py``), the program
builds its index and starts serving through
``repro.api.serve(h, backend=..., config=ServiceConfig(...))``, and every
bucket shape the cell's traffic can make the admission loop dispatch is
run once through the service itself, so the window finds every program
compiled or loaded from the persistent cache.

The window drives ``ReachabilityService.submit`` with the cell's traffic
(``loadgen.py``) for ``seconds``.  With ``trace`` a profiler records a
steady stretch of it.  Then every answer owed is awaited, the peak
device memory is read, the program's state is freed, and only then the
plain reference (named by the configuration, ``reference.py``) computes
the expected answer of every request of the window.  ``correct`` means
no answer differs and none failed or never came.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import threading
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Optional

import numpy as np

import compile_clock
import hypergraphs
import loadgen
import manifest
import tracefile

__all__ = ["run", "emit", "start_serving", "request_factory",
           "JOIN_PROGRAMS"]

# the programs that answer a batch: the XLA join and, on the kernel
# path, the row gather feeding the Pallas label join
JOIN_PROGRAMS = ("batched_mr", "_gather_rows", "label_join")


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _buckets(min_bucket: int, max_batch: int, largest: int):
    """The padded shapes a group of at most ``largest`` requests can take
    (the service's power-of-two buckets, ``min_bucket`` .. ``max_batch``)."""
    b = min_bucket
    out = [b]
    while b < min(largest, max_batch):
        b = min(2 * b, max_batch)
        out.append(b)
    return out


def _warm(svc, n: int, seed: int, make_request, largest: int) -> None:
    """Run every bucket shape the traffic can dispatch once through the
    service (synchronous mode).  MR and s-reach groups run the same join
    program per shape, so MR batches warm every shape and one small
    s-reach batch warms that kind's host path."""
    rng = np.random.default_rng([int(seed), 0x5741])
    shapes = [(b, True) for b in _buckets(svc.min_bucket, svc.max_batch,
                                          largest)]
    for b, is_mr in shapes + [(svc.min_bucket, False)]:
        us, vs = rng.integers(0, n, b), rng.integers(0, n, b)
        futs = [svc.submit(make_request(is_mr, int(u), int(v), 1))
                for u, v in zip(us, vs)]
        svc.drain()
        for f in futs:
            f.result()


class _Tracer:
    """Profiles ``length`` seconds starting ``lead`` seconds into the
    window, on a thread of its own so the generator never waits for the
    profiler to start."""

    def __init__(self, out: Path, lead: float, length: float):
        self.out, self.lead, self.length = out, lead, length
        self.thread: Optional[threading.Thread] = None
        self.error: Optional[BaseException] = None

    def start(self, t0: float) -> None:
        self.thread = threading.Thread(target=self._run, args=(t0,),
                                       name="bench-tracer", daemon=True)
        self.thread.start()

    def _run(self, t0: float) -> None:
        import jax
        try:
            time.sleep(max(t0 + self.lead - time.perf_counter(), 0.0))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(self.out), profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(tracefile.WINDOW):
                    time.sleep(self.length)
            finally:
                jax.profiler.stop_trace()
        except BaseException as exc:                   # noqa: BLE001
            self.error = exc

    def events(self) -> dict:
        self.thread.join()
        if self.error is not None:
            raise self.error
        (path,) = self.out.glob("plugins/profile/*/*.xplane.pb")
        return tracefile.read_xplane(path)


class _HostWatch:
    """Where the host stood still in the window: a thread that wakes every
    5 ms keeps its latest wake-up, and each more than 50 ms late, and the
    garbage collector's full passes are timed.  Both as ``[seconds into
    the window, seconds]``, the ten longest, for the result's
    ``window``."""

    LATE_S = 0.05

    def __init__(self):
        self.stalls, self.full_gc = [], []
        self.longest = 0.0
        self._stop = threading.Event()
        self._gc_start = None
        self.t0 = None
        self.thread = None

    def _on_gc(self, phase, info):
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self._gc_start is not None:
            self.full_gc.append([self._gc_start - self.t0,
                                 time.perf_counter() - self._gc_start])

    def _run(self):
        step = 0.005
        last = time.perf_counter()
        while not self._stop.wait(step):
            now = time.perf_counter()
            self.longest = max(self.longest, now - last - step)
            if now - last - step > self.LATE_S:
                self.stalls.append([last + step - self.t0,
                                    now - last - step])
            last = now

    def start(self, t0: float) -> None:
        self.t0 = t0
        gc.callbacks.append(self._on_gc)
        self.thread = threading.Thread(target=self._run, daemon=True,
                                       name="bench-hostwatch")
        self.thread.start()

    def stop(self) -> dict:
        self._stop.set()
        self.thread.join()
        gc.callbacks.remove(self._on_gc)
        top = lambda xs: sorted(xs, key=lambda x: -x[1])[:10]
        return {"longest_stall_s": self.longest,
                "host_stalls": top(self.stalls),
                "full_gc": top(self.full_gc),
                "full_gc_count": len(self.full_gc)}


def _device_info(jax, chips: int) -> dict:
    devices = jax.devices()
    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": peak}


def _check(log: loadgen.Log, want: np.ndarray):
    """Counts of answers that differ from ``want`` and of requests that
    failed or got no answer.  An MR answer must be an ``int``, an s-reach
    answer a ``bool``."""
    came = (np.isfinite(log.done) & (log.kind != loadgen.NONE)
            & (log.kind != loadgen.FAILED))
    typed = np.where(log.is_mr, loadgen.INT, loadgen.BOOL)
    wrong = came & ((log.kind != typed) | (log.value != want))
    return int(np.count_nonzero(wrong)), int(np.count_nonzero(~came))


def _by_fifth(log: loadgen.Log) -> dict:
    """Answers completed, and the p99 latency of requests due, in each
    fifth of the window: whether the window is steady."""
    edges = log.t0 + log.seconds * np.arange(6) / 5
    lat = np.where(np.isfinite(log.done), log.latency_s() * 1e3, np.inf)
    answered, p99 = [], []
    for a, b in zip(edges[:-1], edges[1:]):
        answered.append(int(np.count_nonzero((log.done >= a)
                                             & (log.done < b))))
        due = (log.due >= a) & (log.due < b)
        p99.append(float(np.percentile(lat[due], 99)) if due.any()
                   else None)
    return {"answered": answered, "latency_p99_ms": p99}


def _served(log: loadgen.Log, loop: str):
    """Answers served and the seconds they took.  An open loop: the answers
    inside the window, over the window.  A closed loop keeps its requests
    in flight, work sent ahead of its answers; its window closes when
    the time is up and all that was sent is answered, so every answer
    after the window opened counts, over the time to the last one."""
    if loop == "closed":
        after = np.isfinite(log.done) & (log.done >= log.t0)
        end = float(np.max(log.done[after])) if after.any() else log.t0
        return int(np.count_nonzero(after)), max(end - log.t0, 1e-9)
    return log.completed_in_window(), float(log.seconds)


def request_factory():
    """``make_request(is_mr, u, v, s)``: the program's typed request."""
    from repro.api import MRRequest, SReachRequest

    def make_request(is_mr, u, v, s):
        return MRRequest(u, v) if is_mr else SReachRequest(u, v, s)
    return make_request


def start_serving(conf: dict, seed: int, largest: int):
    """Draw the configuration's hypergraph from ``seed``, build the
    program's index behind ``serve`` (admission thread not started yet),
    and warm every bucket shape a group of up to ``largest`` requests
    can take.  Returns the service, ``(n, e_ptr, e_idx)`` for the
    reference, and the seconds of each part of this set-up."""
    from repro.api import ServiceConfig, from_edge_lists, serve
    t_graph = time.perf_counter()
    n, e_ptr, e_idx = hypergraphs.make_edges(conf["hypergraph"], seed)
    h = from_edge_lists(hypergraphs.edge_lists(e_ptr, e_idx), n=n)
    t_build = time.perf_counter()
    svc = serve(h, backend=conf["backend"],
                config=ServiceConfig(**conf["service"]), start=False)
    svc.engine.block_until_built()
    t_warm = time.perf_counter()
    if svc.engine.name != conf["expect_backend"]:
        raise RuntimeError(f"the program picked {svc.engine.name!r}, the "
                           f"configuration states "
                           f"{conf['expect_backend']!r}")
    _warm(svc, n, seed, request_factory(), largest)
    parts = {"graph": t_build - t_graph, "build": t_warm - t_build,
             "warm": time.perf_counter() - t_warm}
    return svc, (n, e_ptr, e_idx), parts


def run(cell: manifest.Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, work_dir: Path, control: Optional[str] = None):
    """Run ``cell`` once; returns the result line as a dict.

    ``control="ladder"`` replaces the program's answers by the
    reference's control (``reference.control``) before the check: the
    run must then read as not correct.  The program's own check is kept
    under ``program_checks``."""
    import jax

    clock = compile_clock.CompileClock()
    conf = cell.config
    traffic = loadgen.Traffic.from_dict(cell.traffic)
    parts = {"start": time.perf_counter() - t_start}
    # a closed loop never has more requests queued than it keeps in flight
    largest = (traffic.outstanding if traffic.loop == "closed"
               else conf["service"]["max_batch"])
    svc, (n, e_ptr, e_idx), more = start_serving(conf, seed, largest)
    parts.update(more)
    backend = svc.engine.name
    lengths = np.asarray(svc.engine.snapshot().lengths, np.int64)

    tracer = None
    if trace:
        out = Path(work_dir) / "trace"
        shutil.rmtree(out, ignore_errors=True)
        out.mkdir(parents=True)
        tracer = _Tracer(out, lead=0.3 * seconds, length=0.4 * seconds)
    stats0 = svc.stats()
    gc.collect()
    svc.start()
    marks = {}

    watch = _HostWatch()

    def on_start(t0):
        watch.start(t0)
        marks["setup_s"] = t0 - t_start
        marks["compile_setup_s"] = clock.seconds(end=t0)
        if tracer is not None:
            tracer.start(t0)

    # a closed loop's window opens once a full batch has come back, so
    # that every batch it times is taken from a full queue
    log = loadgen.drive(svc, traffic, seed, n, seconds,
                        make_request=request_factory(), on_start=on_start,
                        prime=min(largest, conf["service"]["max_batch"]))
    svc.close()
    stats1 = svc.stats()
    host = watch.stop()
    served, served_s = _served(log, traffic.loop)
    window_end = log.t0 + max(seconds, served_s)
    compiles_in_window = clock.count(log.t0, window_end)
    device = _device_info(jax, cell.chips)
    summary = None
    if tracer is not None:
        events = tracer.events()
        with open(Path(work_dir) / "trace" / "events.json", "w") as f:
            json.dump(events, f)
        summary = tracefile.summarize(events, JOIN_PROGRAMS)
    del svc
    gc.collect()

    t_check = time.perf_counter()
    ref = _load(Path(manifest.BENCH) / conf["reference"], "bench_reference")
    mr = ref.max_reach(n, e_ptr, e_idx, log.u, log.v)
    want = ref.answers(mr, log.is_mr, log.s)
    mismatched, failed = _check(log, want)
    check_s = time.perf_counter() - t_check
    program_checks = None
    if control == "ladder":
        # the program's own reading is kept beside the control's
        program_checks = {"mismatched": {"value": mismatched, "limit": 0},
                          "failed": {"value": failed, "limit": 0}}
        got = ref.answers(ref.control(mr), log.is_mr, log.s)
        mismatched, failed = int(np.count_nonzero(got != want)), 0
    elif control is not None:
        raise ValueError(f"unknown control {control!r}")

    ctx = SimpleNamespace(
        loop=traffic.loop, seconds=float(seconds),
        setup_s=marks["setup_s"], build_s=parts["build"],
        compile_setup_s=marks["compile_setup_s"],
        latency_ms=np.where(np.isfinite(log.done), log.latency_s() * 1e3,
                            np.inf),
        lag_ms=log.lag_s() * 1e3,
        completed_in_window=log.completed_in_window(),
        served=served, served_s=served_s,
        longest_stall_s=host["longest_stall_s"],
        answered=int(stats1.answered - stats0.answered),
        batches=int(stats1.batches - stats0.batches),
        join_bytes_per_request=float(np.mean(
            8 * (lengths[log.u] + lengths[log.v]))) if log.count else 0.0,
        trace=summary, device_kind=device["kind"])
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        reader = _load(manifest.reader_path(m["name"]),
                       "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {"correct": mismatched == 0 and failed == 0,
              "attempted": log.count, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in summary.device_ops],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps]}
    result["window"] = {
        "requests": log.count, "answered_in_window":
            log.completed_in_window(),
        "served": served, "served_s": served_s,
        "compiles_in_window": compiles_in_window,
        "compiles_before_window": clock.count(end=log.t0),
        "persistent_cache_hits": clock.cache_hits,
        "batches": ctx.batches, "backend": backend,
        "label_width_max": int(lengths.max()),
        "setup_parts_s": parts, "check_s": check_s,
        "by_fifth": _by_fifth(log), **host}
    if program_checks is not None:
        result["program_checks"] = program_checks
    result["checks"] = {
        "mismatched": {"value": mismatched, "limit": 0},
        "failed": {"value": failed, "limit": 0}}
    return result


def emit(result: dict) -> None:
    """The check's numbers as the last lines of standard error, the
    result as the last line of standard output."""
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
