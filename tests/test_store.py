"""Persistence subsystem (repro.store): on-disk format round trips,
WAL semantics, checkpoint/replay via IndexStore, and HIF import/export.

The crash-under-fire path (SIGKILL mid-stream) lives in
tests/test_crash_recovery.py; the restored engines' full op-set
conformance vs the mst-oracle lives in tests/test_conformance.py
(rows ``hl-index[restored]`` / ``sharded[restored]``).
"""
import json
import os

import numpy as np
import pytest

from repro.api import build_engine, random_hypergraph, serve
from repro.core.hypergraph import neighbor_csr
from repro.serve.reach_service import ReachabilityService
from repro.store import (FORMAT_REGISTRY, FORMAT_VERSION, CorruptStore,
                         IndexStore, StoreError, StoreUnsupported,
                         WriteAheadLog, load_index, load_segments,
                         read_hif, read_manifest, save_index, scan_wal,
                         write_hif)


def _graph():
    return random_hypergraph(36, 48, seed=5)


def _queries(h, q=64, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, h.n, q), rng.integers(0, h.n, q)


def _memmap_backed(a: np.ndarray) -> bool:
    while a is not None:
        if isinstance(a, np.memmap):
            return True
        a = a.base
    return False


# ---------------------------------------------------------------------------
# format: save/load round trips
# ---------------------------------------------------------------------------

def test_format_registry_names_current_version():
    assert FORMAT_VERSION in FORMAT_REGISTRY


@pytest.mark.parametrize("backend,opts", [
    ("hl-index", {}),
    ("hl-index", {"minimize_labels": False}),
    ("hl-index", {"construction": "sharded", "workers": 2}),
    ("hl-index-basic", {}),
    ("hl-index-basic", {"cover_check": False}),
    ("closure", {}),
])
def test_round_trip_byte_identical(tmp_path, backend, opts):
    h = _graph()
    eng = build_engine(h, backend, **opts)
    p = tmp_path / "x.hlidx"
    save_index(p, eng)
    eng2 = load_index(p)
    assert eng2.name == backend
    assert eng2.version == eng.version == 0
    # graph arrays
    for f in ("e_ptr", "e_idx", "v_ptr", "v_idx"):
        a, b = getattr(eng.h, f), getattr(eng2.h, f)
        assert a.dtype == b.dtype and np.array_equal(a, b)
    if backend == "closure":
        a, b = np.asarray(eng.rows), np.asarray(eng2.rows)
        assert a.dtype == b.dtype and np.array_equal(a, b)
    else:
        # the tentpole claim: loaded labels byte-identical to built ones
        assert np.array_equal(eng.idx.rank, eng2.idx.rank)
        assert np.array_equal(eng.idx.perm, eng2.idx.perm)
        for u in range(h.n):
            for attr in ("labels_edge", "labels_rank", "labels_s"):
                a = getattr(eng.idx, attr)[u]
                b = getattr(eng2.idx, attr)[u]
                assert a.dtype == b.dtype and np.array_equal(a, b)
        # zero-copy: label arrays are views into the file mmap, so the
        # restart path is page-in + to_mesh, not a rebuild
        assert _memmap_backed(eng2.idx.rank)
        assert _memmap_backed(eng2.idx.labels_s[0])
    us, vs = _queries(h)
    assert np.array_equal(eng.mr_batch(us, vs), eng2.mr_batch(us, vs))


def test_restored_update_path_keeps_builder(tmp_path):
    """A restored engine continues scoped maintenance with the same
    builder/minimizer options it was built with."""
    h = _graph()
    eng = build_engine(h, "hl-index", construction="sharded", workers=2)
    save_index(tmp_path / "x.hlidx", eng)
    eng2 = load_index(tmp_path / "x.hlidx")
    assert eng2.construction == "sharded"
    for e in (eng, eng2):
        e.update(inserts=[[1, 2, 3]], deletes=[0])
    assert eng2.version == 1
    us, vs = _queries(eng.h)
    assert np.array_equal(eng.mr_batch(us, vs), eng2.mr_batch(us, vs))


def test_sharded_round_trip_all_payloads(tmp_path):
    h = _graph()
    us, vs = _queries(h)
    # closure-resident regime
    eng = build_engine(h, "sharded")
    save_index(tmp_path / "c.hlidx", eng)
    m1 = read_manifest(tmp_path / "c.hlidx")
    assert m1["payload"] == "closure"
    r1 = load_index(tmp_path / "c.hlidx")
    assert np.array_equal(eng.mr_batch(us, vs), r1.mr_batch(us, vs))
    # snapshot regime (snapshot() frees the closure)
    eng.snapshot()
    save_index(tmp_path / "s.hlidx", eng)
    assert read_manifest(tmp_path / "s.hlidx")["payload"] == "snapshot"
    r2 = load_index(tmp_path / "s.hlidx")
    assert np.array_equal(eng.mr_batch(us, vs), r2.mr_batch(us, vs))
    # label regime
    eng = build_engine(h, "sharded", build_labels=True)
    save_index(tmp_path / "l.hlidx", eng)
    assert read_manifest(tmp_path / "l.hlidx")["payload"] == "labels"
    r3 = load_index(tmp_path / "l.hlidx")
    assert np.array_equal(eng.mr_batch(us, vs), r3.mr_batch(us, vs))
    r3.update(inserts=[[4, 5, 6]])
    eng.update(inserts=[[4, 5, 6]])
    assert np.array_equal(eng.mr_batch(us, vs), r3.mr_batch(us, vs))


def test_neighbor_csr_block_round_trip(tmp_path):
    h = _graph()
    eng = build_engine(h, "hl-index")
    nbr = neighbor_csr(h)
    save_index(tmp_path / "x.hlidx", eng, neighbors=nbr)
    _, seg = load_segments(tmp_path / "x.hlidx")
    assert np.array_equal(seg["nbr.ptr"], nbr.ptr)
    assert np.array_equal(seg["nbr.idx"], nbr.idx)
    assert np.array_equal(seg["nbr.od"], nbr.od)


@pytest.mark.parametrize("backend", ["online", "frontier", "mst-oracle"])
def test_index_free_backends_unsupported(tmp_path, backend):
    eng = build_engine(_graph(), backend)
    with pytest.raises(StoreUnsupported):
        save_index(tmp_path / "x.hlidx", eng)


# ---------------------------------------------------------------------------
# format: corruption detection
# ---------------------------------------------------------------------------

def _flip_byte(path, offset):
    with open(path, "r+b") as f:
        f.seek(offset)
        b = f.read(1)
        f.seek(offset)
        f.write(bytes([b[0] ^ 0xFF]))


def test_bad_magic_rejected(tmp_path):
    p = tmp_path / "x.hlidx"
    save_index(p, build_engine(_graph(), "hl-index"))
    _flip_byte(p, 0)
    with pytest.raises(CorruptStore, match="magic"):
        load_index(p)


def test_unknown_format_version_rejected(tmp_path):
    p = tmp_path / "x.hlidx"
    save_index(p, build_engine(_graph(), "hl-index"))
    _flip_byte(p, 8)                      # the u32 format version field
    with pytest.raises(CorruptStore, match="format version"):
        load_index(p)


def test_truncated_file_fails_manifest_crc(tmp_path):
    p = tmp_path / "x.hlidx"
    save_index(p, build_engine(_graph(), "hl-index"))
    with open(p, "r+b") as f:
        f.truncate(os.path.getsize(p) - 7)
    with pytest.raises(CorruptStore):
        load_index(p)


def test_corrupt_segment_detected_by_checksum(tmp_path):
    p = tmp_path / "x.hlidx"
    manifest = save_index(p, build_engine(_graph(), "hl-index"))
    seg = next(s for s in manifest["segments"] if s["name"] == "labels.s")
    _flip_byte(p, seg["offset"])
    with pytest.raises(CorruptStore, match="labels.s"):
        load_index(p, verify=True)
    load_index(p, verify=False)           # lazy mode defers integrity


def test_expect_backend_mismatch(tmp_path):
    p = tmp_path / "x.hlidx"
    save_index(p, build_engine(_graph(), "closure"))
    with pytest.raises(StoreError, match="closure"):
        load_index(p, expect_backend="hl-index")


# ---------------------------------------------------------------------------
# build_engine(restore=...)
# ---------------------------------------------------------------------------

def test_build_engine_restore_from_file(tmp_path):
    h = _graph()
    eng = build_engine(h, "hl-index")
    p = tmp_path / "x.hlidx"
    save_index(p, eng)
    eng2 = build_engine(restore=p)
    us, vs = _queries(h)
    assert np.array_equal(eng.mr_batch(us, vs), eng2.mr_batch(us, vs))
    # non-auto backend asserts what the checkpoint must hold
    with pytest.raises(StoreError):
        build_engine(backend="sharded", restore=p)


def test_build_engine_argument_validation(tmp_path):
    h = _graph()
    with pytest.raises(ValueError, match="ambiguous"):
        build_engine(h, restore=tmp_path / "x.hlidx")
    with pytest.raises(ValueError, match="hypergraph"):
        build_engine()


# ---------------------------------------------------------------------------
# write-ahead log
# ---------------------------------------------------------------------------

def test_wal_append_scan_round_trip(tmp_path):
    p = tmp_path / "w.log"
    with WriteAheadLog(p) as wal:
        wal.append(1, [[1, 2, 3]], [])
        wal.append(2, [], [0, 4])
        wal.append(3, [[5, 6], [7, 8]], [2])
    records, _, status = scan_wal(p)
    assert status == "ok"
    assert records == [(1, [[1, 2, 3]], []), (2, [], [0, 4]),
                       (3, [[5, 6], [7, 8]], [2])]


def test_wal_monotonic_versions_enforced(tmp_path):
    with WriteAheadLog(tmp_path / "w.log", base_version=5) as wal:
        with pytest.raises(StoreError, match="monotonic"):
            wal.append(5, [], [0])
        with pytest.raises(StoreError, match="monotonic"):
            wal.append(7, [], [0])
        wal.append(6, [], [0])
        assert wal.last_version == 6


@pytest.mark.parametrize("mutilate,expect", [
    (lambda data: data[:-3], "torn-payload"),
    (lambda data: data + b"\x01\x02\x03", "torn-header"),
    (lambda data: data + b"\x00" * 40, "bad-magic"),
])
def test_wal_torn_tail_dropped_not_fatal(tmp_path, mutilate, expect):
    p = tmp_path / "w.log"
    with WriteAheadLog(p) as wal:
        wal.append(1, [[1, 2]], [])
        wal.append(2, [[3, 4]], [])
    data = p.read_bytes()
    p.write_bytes(mutilate(data))
    records, valid, status = scan_wal(p)
    assert status == expect
    assert [r[0] for r in records] == ([1] if expect == "torn-payload"
                                       else [1, 2])
    # reopening truncates the tail for good and resumes the lineage
    with WriteAheadLog(p) as wal:
        assert os.path.getsize(p) == valid
        assert wal.last_version == records[-1][0]
        wal.append(records[-1][0] + 1, [[9]], [])
    assert scan_wal(p)[2] == "ok"


def test_wal_flipped_payload_byte_is_bad_checksum(tmp_path):
    p = tmp_path / "w.log"
    with WriteAheadLog(p) as wal:
        wal.append(1, [[1, 2]], [])
    data = bytearray(p.read_bytes())
    data[-1] ^= 0xFF
    p.write_bytes(bytes(data))
    records, _, status = scan_wal(p)
    assert status == "bad-checksum" and records == []


# ---------------------------------------------------------------------------
# engine WAL hook ordering
# ---------------------------------------------------------------------------

def test_rejected_update_is_never_journaled(tmp_path):
    h = _graph()
    eng = build_engine(h, "hl-index")
    store = IndexStore(tmp_path / "s")
    store.attach(eng)
    wal_path = store.path / "wal-000000000000.log"
    with pytest.raises(IndexError):
        eng.update(deletes=[h.m + 3])     # validated before journaling
    assert eng.version == 0
    assert scan_wal(wal_path)[0] == []
    eng.update(inserts=[[0, 1, 2]])
    assert [r[0] for r in scan_wal(wal_path)[0]] == [1]


def test_unsupported_backend_gates_before_journal(tmp_path):
    from repro.core.engine import UpdateUnsupported
    eng = build_engine(_graph(), "mst-oracle")
    with pytest.raises(UpdateUnsupported):
        eng.update(inserts=[[1, 2]])
    assert eng.version == 0


# ---------------------------------------------------------------------------
# IndexStore: checkpoint / replay / compaction
# ---------------------------------------------------------------------------

def _stream(eng, k, seed=11):
    rng = np.random.default_rng(seed)
    for i in range(k):
        ins = [sorted(int(x) for x in rng.choice(eng.h.n, 3, replace=False))]
        dels = [int(rng.integers(0, eng.h.m))] if i % 3 == 2 else []
        eng.update(inserts=ins, deletes=dels)


def test_store_checkpoint_replay_matches_live(tmp_path):
    h = _graph()
    eng = build_engine(h, "hl-index")
    store = IndexStore(tmp_path / "s")
    store.attach(eng)                     # seeds checkpoint-0
    _stream(eng, 6)
    assert eng.version == 6
    eng2 = IndexStore(tmp_path / "s").restore()
    assert eng2.version == 6
    us, vs = _queries(eng.h)
    assert np.array_equal(eng.mr_batch(us, vs), eng2.mr_batch(us, vs))
    # the restored engine resumes the lineage: next update journals
    eng2.update(inserts=[[0, 1]])
    assert eng2.version == 7


def test_store_compaction_truncates_log(tmp_path):
    h = _graph()
    eng = build_engine(h, "hl-index")
    store = IndexStore(tmp_path / "s", checkpoint_every=3)
    store.attach(eng)
    _stream(eng, 7)
    assert store.checkpoint_version == 6  # compacted at 3 and 6
    files = sorted(os.listdir(store.path))
    assert sum(f.startswith("checkpoint-") for f in files) == 1
    assert sum(f.startswith("wal-") for f in files) == 1
    assert store.records_since_checkpoint == 1
    eng2 = IndexStore(tmp_path / "s").restore()
    assert eng2.version == 7
    us, vs = _queries(eng.h)
    assert np.array_equal(eng.mr_batch(us, vs), eng2.mr_batch(us, vs))


def test_store_lineage_mismatch_rejected(tmp_path):
    h = _graph()
    eng = build_engine(h, "hl-index")
    store = IndexStore(tmp_path / "s")
    store.attach(eng)
    eng.update(inserts=[[0, 1, 2]])
    store.close()
    stranger = build_engine(h, "hl-index")   # version 0, store is at 1
    with pytest.raises(StoreError, match="lineage"):
        IndexStore(tmp_path / "s").attach(stranger)


def test_store_restore_empty_dir_is_error(tmp_path):
    with pytest.raises(StoreError, match="nothing to restore"):
        IndexStore(tmp_path / "empty").restore()


def test_store_restore_detects_lineage_gap(tmp_path):
    h = _graph()
    eng = build_engine(h, "hl-index")
    store = IndexStore(tmp_path / "s")
    store.attach(eng)
    eng.update(inserts=[[0, 1]])
    eng.update(inserts=[[2, 3]])
    store.close()
    # forge a gap: rewrite the log with only record 2
    wal_path = store.path / "wal-000000000000.log"
    records = scan_wal(wal_path)[0]
    wal_path.unlink()
    with WriteAheadLog(wal_path, base_version=1) as w:
        v, ins, dels = records[1]
        w.append(v, ins, dels)
    with pytest.raises(CorruptStore, match="lineage gap"):
        IndexStore(tmp_path / "s").restore()


# ---------------------------------------------------------------------------
# service checkpoint / restore
# ---------------------------------------------------------------------------

def test_service_checkpoint_restore_round_trip(tmp_path):
    h = _graph()
    svc = serve(h, "hl-index", start=False)
    store = IndexStore(tmp_path / "s")
    assert svc.checkpoint(store) == 0
    svc.update(inserts=[[1, 2, 3]])
    svc.update(deletes=[0])
    store.close()
    svc2 = ReachabilityService.restore(tmp_path / "s", start=False)
    assert svc2.engine.version == 2
    us, vs = _queries(svc.engine.h, q=32)
    futs_a = [svc.mr(int(u), int(v)) for u, v in zip(us, vs)]
    futs_b = [svc2.mr(int(u), int(v)) for u, v in zip(us, vs)]
    svc.drain(), svc2.drain()
    assert [f.result() for f in futs_a] == [f.result() for f in futs_b]
    svc.close(), svc2.close()


# ---------------------------------------------------------------------------
# HIF import/export
# ---------------------------------------------------------------------------

def _hif_doc():
    return {
        "network-type": "undirected",
        "metadata": {"name": "fixture"},
        # "iso" never appears in an incidence: isolated vertex
        "nodes": [{"node": "a"}, {"node": "b"}, {"node": "iso"},
                  {"node": "c"}],
        # e1 and e2 have identical member sets (duplicate-member
        # hyperedges — both must survive); "hollow" has no incidences
        "edges": [{"edge": "e1"}, {"edge": "e2"}, {"edge": "e3"},
                  {"edge": "hollow"}],
        "incidences": [
            {"edge": "e1", "node": "a"}, {"edge": "e1", "node": "b"},
            {"edge": "e2", "node": "a"}, {"edge": "e2", "node": "b"},
            {"edge": "e3", "node": "b"}, {"edge": "e3", "node": "c"},
            {"edge": "e3", "node": "b"},   # within-edge duplicate incidence
        ],
    }


def test_hif_import(tmp_path):
    p = tmp_path / "t.hif.json"
    p.write_text(json.dumps(_hif_doc()))
    h = read_hif(p)
    assert h.n == 4                       # incl. the isolated vertex
    assert h.m == 3                       # the memberless edge is dropped
    sets = [set(h.e_idx[h.e_ptr[e]:h.e_ptr[e + 1]].tolist())
            for e in range(h.m)]
    assert sets[0] == sets[1] == {0, 1}   # duplicate-member pair survives
    assert sets[2] == {1, 3}              # within-edge duplicate collapsed


def test_hif_round_trip_identity(tmp_path):
    p = tmp_path / "t.hif.json"
    p.write_text(json.dumps(_hif_doc()))
    h1 = read_hif(p)
    write_hif(tmp_path / "out.hif.json", h1, metadata={"pass": 1})
    h2 = read_hif(tmp_path / "out.hif.json")
    write_hif(tmp_path / "out2.hif.json", h2)
    h3 = read_hif(tmp_path / "out2.hif.json")
    for a, b in ((h1, h2), (h2, h3)):
        assert a.n == b.n and a.m == b.m
        for f in ("e_ptr", "e_idx", "v_ptr", "v_idx"):
            assert np.array_equal(getattr(a, f), getattr(b, f))


def test_hif_rejects_directed_and_garbage(tmp_path):
    p = tmp_path / "d.hif.json"
    p.write_text(json.dumps({"network-type": "directed", "incidences": []}))
    with pytest.raises(ValueError, match="directed"):
        read_hif(p)
    p2 = tmp_path / "g.hif.json"
    p2.write_text(json.dumps({"nodes": []}))
    with pytest.raises(ValueError, match="incidences"):
        read_hif(p2)


def test_hif_through_make_dataset(tmp_path):
    h = random_hypergraph(20, 25, seed=9)
    p = tmp_path / "ds.hif.json"
    write_hif(p, h)
    h2 = read_hif(str(p))
    assert h2.n == h.n and h2.m == h.m
    for f in ("e_ptr", "e_idx", "v_ptr", "v_idx"):
        assert np.array_equal(getattr(h, f), getattr(h2, f))
    with pytest.raises(FileNotFoundError):
        read_hif(str(tmp_path / "missing.hif.json"))
    # an engine built from the imported graph answers like the original
    a = build_engine(h, "hl-index")
    b = build_engine(h2, "hl-index")
    us, vs = _queries(h, q=32)
    assert np.array_equal(a.mr_batch(us, vs), b.mr_batch(us, vs))
