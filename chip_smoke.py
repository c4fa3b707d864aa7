#!/usr/bin/env python3
"""Smoke run of the served reachability path on a TPU.

    python3 chip_smoke.py              # phases (a)-(c), one chip
    python3 chip_smoke.py --chips 4    # the mesh phase alone, four chips

(a) Main path.  The walmart-trips analog at its published scale (88,860
    vertices x 69,906 hyperedges of 2-8 vertices) served through
    ``serve(h, backend="auto")``.  The planner must pick ``hl-index``.
    16,384 mixed MR / s-reach requests; every answer is checked against
    the independent ``mst-oracle``.
(b) Kernel path.  The same engine served with ``use_kernels=True``.  The
    Pallas label join must run compiled (no interpreter, a
    ``tpu_custom_call`` in its HLO) and answer byte-identically to (a).
(c) Closure regime.  ``sharded`` on a 1x1 mesh with kernels at
    m = 8192 (W* is f32 [8192, 8192], 256 MiB), WAL-attached through an
    ``IndexStore`` and served by two replicas.  One scoped update while
    serving runs both buffer-donation paths: the in-place patch of the
    resident W* and the dirty-row scatter into each replica.  Answers are
    checked against ``mst-oracle`` before and after the update.
--chips 4: ``sharded`` on a 2x2 mesh at m = 16,384 (W* 1 GiB, 256 MiB
    per chip) for both collective schedules, with and without kernels,
    against ``closure`` on one device and ``mst-oracle``; every chip
    must hold its W* block.  Then the sharded HL-index construction of
    the walmart analog cut into four disjoint regions (forked worker
    pool, started while JAX holds the chips) must reproduce the serial
    labels byte for byte with no pool fallback.

All data comes from ``--seed``.  The lines before the last are smoke
timings, not benchmark numbers.  The last line is one JSON object with
the device.  The script exits non-zero and prints no result when JAX
finds no TPU, or when it runs outside a checkout of this repository.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

# walmart-trips (Benson et al.) at its published scale; benchmarks/
# datasets.py keeps a CPU-sized "WA-s" stand-in of the same shape
WALMART = dict(n=88_860, m=69_906, min_size=2, max_size=8)
CLOSURE_M = 8192          # phase (c): W* f32 [8192, 8192] = 256 MiB
MESH_M = 16_384           # --chips 4: W* 1 GiB, 256 MiB per chip
MAX_BATCH = 4096


def log(msg: str) -> None:
    print(msg, flush=True)


class Clock:
    """Per-phase wall time, JAX compile time and device memory."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        import jax
        c0, h0, t0 = self.compile_s, self.cache_hits, time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        mem = []
        for d in jax.local_devices():
            st = d.memory_stats() or {}
            mem.append(f"{st.get('bytes_in_use', 0)}/"
                       f"{st.get('peak_bytes_in_use', 0)}")
        log(f"smoke timing (not a benchmark number): phase={name} "
            f"wall_s={wall:.3f} compile_s={self.compile_s - c0:.3f} "
            f"cache_hits={self.cache_hits - h0} "
            f"device_bytes_in_use/peak={','.join(mem)}")


def _requests(rng, n: int, count: int, hot=()):
    """Mixed MR / s-reach traffic: uniform pairs, s in 1..4, and the
    vertices in ``hot`` paired with random partners."""
    from repro.api import MRRequest, SReachRequest
    us = rng.integers(0, n, count)
    vs = rng.integers(0, n, count)
    hot = list(hot)
    us[:len(hot)] = hot
    ss = rng.integers(1, 5, count)
    mr = rng.random(count) < 0.5
    return [MRRequest(int(u), int(v)) if k else SReachRequest(int(u), int(v),
                                                              int(s))
            for u, v, s, k in zip(us, vs, ss, mr)]


def _ask(svc, reqs):
    # .result() on every future: an error raised in dispatch fails the run
    return [f.result() for f in svc.submit_many(reqs)]


def _check_oracle(oracle, reqs, answers) -> int:
    for r, a in zip(reqs, answers):
        mr = oracle.mr(r.u, r.v)
        want = mr if r.kind == "mr" else mr >= r.s
        if type(a) is not type(want) or a != want:
            raise AssertionError(f"{r} answered {a!r}, mst-oracle says "
                                 f"{want!r}")
    return len(reqs)


def _same(a, b) -> bool:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    return (a.dtype == b.dtype and a.shape == b.shape
            and a.tobytes() == b.tobytes())


def phase_main_path(clock, seed: int, n_requests: int = 16_384,
                    graph=WALMART):
    """(a): returns (engine, requests, answers) for phase (b)."""
    import numpy as np
    from repro.api import build_engine, random_hypergraph, serve, \
        ServiceConfig
    rng = np.random.default_rng(seed)
    with clock.phase("a.build"):
        h = random_hypergraph(graph["n"], graph["m"],
                              min_size=graph["min_size"],
                              max_size=graph["max_size"], seed=seed)
        svc = serve(h, backend="auto",
                    config=ServiceConfig(max_batch=MAX_BATCH))
    eng = svc.engine
    if eng.name != "hl-index":
        raise AssertionError(f"planner picked {eng.name!r}, not hl-index")
    reqs = _requests(rng, h.n, n_requests)
    with svc, clock.phase("a.serve"):
        answers = _ask(svc, reqs)
    st = svc.stats()
    with clock.phase("a.oracle"):
        oracle = build_engine(h, "mst-oracle")
        checked = _check_oracle(oracle, reqs, answers)
    log(f"phase a: n={h.n} m={h.m} nnz={h.nnz} backend={eng.name} "
        f"lmax={eng.snapshot().lmax} requests={len(reqs)} "
        f"batches={st.batches} checked_vs_mst_oracle={checked} ok")
    return eng, reqs, answers


def phase_kernel_path(clock, eng, reqs, answers):
    """(b): the same engine and requests through the Pallas label join."""
    import jax
    import jax.numpy as jnp
    from repro.api import serve, ServiceConfig
    from repro.core.query import KernelSnapshot
    from repro.kernels.label_join import label_join_pallas
    from repro.kernels.ops import use_interpret
    with clock.phase("b.serve"):
        with serve(eng, config=ServiceConfig(max_batch=MAX_BATCH,
                                             use_kernels=True)) as svc:
            got = _ask(svc, reqs)
        st = svc.stats()
    if st.kernel_batches == 0 or st.kernel_batches != st.batches:
        raise AssertionError(f"{st.kernel_batches} of {st.batches} batches "
                             f"went through the kernel")
    if [(type(a), a) for a in got] != [(type(a), a) for a in answers]:
        raise AssertionError("kernel answers differ from batched_mr's")
    view = KernelSnapshot(eng.snapshot())
    if view.interpret is not use_interpret():
        raise AssertionError("the kernel view picked the wrong mode")
    rows = jax.ShapeDtypeStruct((MAX_BATCH, view.lmax), jnp.int32)
    hlo = label_join_pallas.lower(rows, rows, rows, rows,
                                  interpret=view.interpret).compile()
    custom = "tpu_custom_call" in hlo.as_text()
    if not view.interpret and not custom:
        raise AssertionError("no tpu_custom_call in the label-join HLO")
    log(f"phase b: interpret={view.interpret} tpu_custom_call={custom} "
        f"kernel_batches={st.kernel_batches} identical_to_a={len(got)} ok")


def _scoped_edit(h):
    """A scoped update: delete a hyperedge of the smallest line-graph
    component and insert one over isolated vertices (joined to the next
    smallest component, when there is one).  The re-closed scope is a
    few hyperedges, and the insert reuses the freed W* slot, so the
    resident geometry does not change."""
    import numpy as np
    from repro.core.hypergraph import neighbor_csr
    comp = neighbor_csr(h).components()
    sizes = np.bincount(comp)
    small = [int(np.nonzero(comp == c)[0][0])
             for c in np.argsort(sizes, kind="stable")[:2]
             if sizes[c] < h.m // 2]
    isolated = np.nonzero(h.vertex_degrees == 0)[0][:5]
    if not small or isolated.size < 5:
        raise AssertionError("the closure graph has no room for a scoped "
                             "edit; pick another --seed")
    insert = set(isolated.tolist())
    if len(small) > 1:
        insert = (set(isolated[:3].tolist())
                  | set(h.edge(small[1])[:2].tolist()))
    insert = sorted(insert)
    return [insert], [small[0]], insert


def phase_closure_regime(clock, seed: int, m: int = CLOSURE_M,
                         n_requests: int = 2048):
    """(c): sharded closure on a 1x1 mesh, replicas, one scoped update."""
    import numpy as np
    from repro.api import (build_engine, IndexStore, make_mesh,
                           random_hypergraph, serve, ServiceConfig)
    rng = np.random.default_rng(seed + 1)
    h = random_hypergraph(2 * m, m, min_size=2, max_size=8, seed=seed + 1)
    mesh = make_mesh((1, 1), ("data", "model"))
    with clock.phase("c.build"):
        eng = build_engine(h, "sharded", mesh=mesh, use_kernels=True)
        eng.block_until_built()
    inserts, deletes, touched = _scoped_edit(h)
    with tempfile.TemporaryDirectory(dir=ROOT, prefix=".smoke-store-") as d:
        # WAL attached: the resident W* is kept for in-place patches
        IndexStore(d).attach(eng)
        cfg = ServiceConfig(replicas=2, max_batch=1024)
        with serve(eng, config=cfg, mesh=mesh) as svc:
            before = _requests(rng, h.n, n_requests)
            with clock.phase("c.serve_before"):
                got = _ask(svc, before)
            checked = _check_oracle(build_engine(h, "mst-oracle"), before,
                                    got)
            w_star = eng._w_star
            replica_ranks = [r.snap.ranks for r in svc.replicas]
            after = _requests(rng, h.n, n_requests, hot=touched)
            with clock.phase("c.update_and_serve"):
                svc.update(inserts=inserts, deletes=deletes)
                got = _ask(svc, after)
            st = svc.stats()
            rst = svc.replica_stats()
        checked += _check_oracle(build_engine(eng.h, "mst-oracle"), after,
                                 got)
    donated_w = w_star.is_deleted()
    donated_r = all(a.is_deleted() for a in replica_ranks)
    on_chip = all(d.platform != "cpu" for d in mesh.devices.flat)
    if on_chip and not (donated_w and donated_r):
        raise AssertionError(f"donation did not happen: W* {donated_w}, "
                             f"replicas {donated_r}")
    if st.mesh_rows_patched == 0 or any(r["full_relands"] != 1
                                        for r in rst):
        raise AssertionError(f"the update re-landed replicas whole: {rst}")
    if st.kernel_batches != st.batches:
        raise AssertionError("closure batches bypassed the kernel")
    log(f"phase c: m={m} n={h.n} W*=f32[{m},{m}] ({4 * m * m >> 20} MiB) "
        f"update=+{len(inserts)}/-{len(deletes)} rows_patched="
        f"{st.mesh_rows_patched} donated_w_star={donated_w} "
        f"donated_replicas={donated_r} checked_vs_mst_oracle={checked} ok")


def _regions(graph, parts: int, seed: int):
    """The walmart analog as ``parts`` disjoint regions (n/parts
    vertices, m/parts hyperedges each, same size law), so construction
    has ``parts`` large line-graph components to shard; one random graph
    at this density is a single component, which leaves a sharded build
    nothing to run in parallel."""
    import numpy as np
    from repro.api import from_edge_lists
    rng = np.random.default_rng(seed)
    nr, mr = graph["n"] // parts, graph["m"] // parts
    edges = [r * nr + rng.choice(nr, int(rng.integers(
                 graph["min_size"], graph["max_size"] + 1)), replace=False)
             for r in range(parts) for _ in range(mr)]
    return from_edge_lists(edges, n=nr * parts)


def phase_mesh(clock, seed: int, m: int = MESH_M, n_queries: int = 1024):
    """--chips 4: sharded closure on 2x2 vs closure and mst-oracle."""
    import jax
    import numpy as np
    from repro.api import build_engine, make_mesh, random_hypergraph
    devices = jax.devices()
    mesh = make_mesh((2, 2), ("data", "model"))
    rng = np.random.default_rng(seed + 2)
    h = random_hypergraph(2 * m, m, min_size=2, max_size=8, seed=seed + 2)
    us = rng.integers(0, h.n, n_queries)
    vs = rng.integers(0, h.n, n_queries)
    with clock.phase("mesh.closure_one_device"):
        ref = build_engine(h, "closure").mr_batch(us, vs)
    oracle = build_engine(h, "mst-oracle")
    want = np.asarray([oracle.mr(int(u), int(v)) for u, v in zip(us, vs)])
    if not np.array_equal(ref, want):
        raise AssertionError("closure disagrees with mst-oracle")
    gc.collect()
    for schedule in ("allgather", "ring"):
        for kernels in (False, True):
            name = f"mesh.sharded.{schedule}.kernels={kernels}"
            with clock.phase(name + ".build"):
                eng = build_engine(h, "sharded", mesh=mesh,
                                   schedule=schedule, use_kernels=kernels)
                eng.block_until_built()
            shards = eng._w_star.addressable_shards
            held = {s.device for s in shards}
            blocks = {tuple(s.data.shape) for s in shards}
            in_use = [(d.memory_stats() or {}).get("bytes_in_use", -1)
                      for d in devices]
            if held != set(devices) or blocks != {(m // 2, m // 2)}:
                raise AssertionError(f"{name}: W* blocks {blocks} on "
                                     f"{len(held)} of {len(devices)} chips")
            if -1 not in in_use and min(in_use) < 4 * (m // 2) ** 2:
                raise AssertionError(f"{name}: a chip holds less than its "
                                     f"W* block: {in_use}")
            with clock.phase(name + ".query"):
                got = eng.mr_batch(us, vs)
            snap_held = {s.device for s in eng.snapshot().svals
                         .addressable_shards}
            if not np.array_equal(got, ref) or snap_held != set(devices):
                raise AssertionError(f"{name}: answers or snapshot "
                                     f"placement wrong")
            log(f"{name}: m={m} W*_block={m // 2}x{m // 2} chips={len(held)} "
                f"bytes_in_use={in_use} answers=closure=mst-oracle "
                f"({n_queries}) ok")
            del eng, shards
            gc.collect()


def phase_mesh_construction(clock, seed: int, graph=WALMART, workers=None):
    """--chips 4: sharded HL-index construction on the 2x2 mesh (forked
    worker pool, started while JAX holds the chips) vs the serial build.
    ``workers=None`` is the engine's default for a four-device mesh."""
    from repro.api import build_engine, make_mesh
    mesh = make_mesh((2, 2), ("data", "model"))
    hw = _regions(graph, 4, seed + 3)
    with clock.phase("mesh.hl-index.serial_build"):
        serial = build_engine(hw, "hl-index")
    with clock.phase("mesh.hl-index.sharded_build"):
        sharded = build_engine(hw, "hl-index", mesh=mesh, workers=workers)
    st = sharded.idx.stats
    if (sharded.construction != "sharded" or st["pool_workers"] < 2
            or st["pool_fallback"] != 0):
        raise AssertionError(f"the shard pool did not run cleanly: {st}")
    a, b = serial.idx, sharded.idx
    same = _same(a.rank, b.rank) and _same(a.perm, b.perm) and all(
        _same(x, y) for fa, fb in ((a.labels_edge, b.labels_edge),
                                   (a.labels_rank, b.labels_rank),
                                   (a.labels_s, b.labels_s),
                                   (a.dual_u, b.dual_u), (a.dual_s, b.dual_s))
        for x, y in zip(fa, fb))
    if not same:
        raise AssertionError("sharded labels differ from the serial build")
    log(f"mesh.hl-index: shards={int(st['shards'])} "
        f"pool_workers={int(st['pool_workers'])} pool_fallback="
        f"{int(st['pool_fallback'])} labels={a.num_labels} "
        f"byte_identical=True ok")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the mesh phase alone, on four chips")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: {SRC / 'repro'} not found; run this script "
              f"from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"this script measures nothing elsewhere", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips; "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} compile_cache={cache}")
    clock = Clock()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(clock, args.seed)
        phase_mesh_construction(clock, args.seed)
    else:
        eng, reqs, answers = phase_main_path(clock, args.seed)
        phase_kernel_path(clock, eng, reqs, answers)
        del eng
        gc.collect()
        phase_closure_regime(clock, args.seed)
    log(f"smoke timing (not a benchmark number): total wall_s="
        f"{time.perf_counter() - t0:.3f} compile_s={clock.compile_s:.3f} "
        f"cache_hits={clock.cache_hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
