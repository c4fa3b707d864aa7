"""Cross-backend conformance matrix.

One parameterized matrix replaces the ad-hoc per-file equivalence checks
that used to live in test_engine.py / test_query_engines.py /
test_serving.py: every registered backend (plus the non-default build
configurations of the sharded-construction paths) × every engine
operation — ``mr``, ``s_reach``, ``mr_batch``, ``s_reach_batch``,
``snapshot``, ``update`` — is validated against the independent
``mst-oracle`` reference on every graph in the suite.

Capability flags are **asserted, never silently skipped**: a backend
with no snapshot form must raise ``SnapshotUnsupported`` (and one with
no update path ``UpdateUnsupported``) exactly where the pinned tables
below say so.  Registry drift — a new backend, or a capability change —
fails the matrix until the expectations here are updated consciously.
"""
import os
import tempfile

import numpy as np
import pytest

from repro.api import (ServiceConfig, available_backends, build_engine,
                       serve, update_capabilities, workload_capabilities,
                       random_hypergraph, planted_chain_hypergraph,
                       from_edge_lists)
from repro.store import load_index, save_index
from repro.core import (MSTOracle, PaddedIndex, apply_edge_edits, build_fast,
                        minimize, mr_oracle_dense,
                        brute_force_mr_from_set, brute_force_mr_set,
                        brute_force_s_distance, brute_force_s_reach_k,
                        brute_force_top_s)
from repro.core.engine import (SnapshotUnsupported, UpdateUnsupported,
                               WorkloadUnsupported, WORKLOAD_OPS)
from repro.serve.reach_service import MRRequest, SReachRequest
from repro.workloads import verify_witness

BACKENDS = available_backends()

# ---------------------------------------------------------------------------
# pinned capability expectations — the registry must match these exactly
# ---------------------------------------------------------------------------

EXPECTED_SNAPSHOT = {
    "hl-index": True, "hl-index-basic": True, "ete": True,
    "closure": True, "sharded": True,
    "online": False, "frontier": False, "threshold": False,
    "mst-oracle": False,
}
EXPECTED_UPDATE = {
    "hl-index": "scoped", "hl-index-basic": "scoped",
    "online": "incremental", "frontier": "incremental",
    "closure": "rebuild", "sharded": "scoped",
    "ete": "unsupported", "threshold": "unsupported",
    "mst-oracle": "unsupported",
}
# workload capability: label ops (witness / mr_set / top_s) need a
# snapshot-capable label or closure form; traversal ops (s_reach_k /
# s_distance) need a live maintained graph.  The static Section IV/VII
# baselines serve neither.
_ALL_OPS = {op: True for op in WORKLOAD_OPS}
_NO_OPS = {op: False for op in WORKLOAD_OPS}
_LABEL_ONLY = dict(_NO_OPS, witness=True, mr_set=True, top_s=True)
_TRAVERSAL_ONLY = dict(_NO_OPS, s_reach_k=True, s_distance=True)
EXPECTED_WORKLOADS = {
    "hl-index": _ALL_OPS, "hl-index-basic": _ALL_OPS,
    "closure": _ALL_OPS, "sharded": _ALL_OPS,
    "ete": _LABEL_ONLY,
    "online": _TRAVERSAL_ONLY, "frontier": _TRAVERSAL_ONLY,
    "threshold": _NO_OPS, "mst-oracle": _NO_OPS,
}

# matrix rows: every registered backend under default options, plus the
# non-default construction paths (sharded label construction; the
# sharded backend's label regime) and the persistence round trip
# (``_restore``: build → save_index → load_index, then the full op set —
# a restored engine meets exactly the same conformance bar as a built
# one) — same bar for all
CONFIGS = {name: (name, {}) for name in BACKENDS}
CONFIGS["hl-index[sharded-build]"] = (
    "hl-index", dict(construction="sharded", num_shards=3))
CONFIGS["sharded[labels]"] = ("sharded", dict(build_labels=True))
CONFIGS["hl-index[restored]"] = ("hl-index", dict(_restore=True))
CONFIGS["sharded[restored]"] = ("sharded", dict(_restore=True))
# kernel rows: the same op set answered through the Pallas device path —
# label_join for batched queries (KernelSnapshot) and maxmin_matmul for
# the sharded closure contraction — pinned to the identical oracle
CONFIGS["hl-index[kernels]"] = ("hl-index", dict(use_kernels=True))
CONFIGS["sharded[kernels]"] = ("sharded", dict(use_kernels=True))
CONFIG_NAMES = sorted(CONFIGS)

# TemporaryDirectory handles for the restored rows: the loaded engines
# hold zero-copy views into the checkpoint mmap, so the files must
# outlive every test that queries them
_RESTORE_DIRS = []


def _build(h, config):
    """Build one engine for a matrix row; ``_restore`` rows round-trip
    it through a persisted checkpoint first."""
    backend, opts = CONFIGS[config]
    opts = dict(opts)
    restore = opts.pop("_restore", False)
    eng = build_engine(h, backend, **opts)
    if restore:
        td = tempfile.TemporaryDirectory()
        _RESTORE_DIRS.append(td)
        path = os.path.join(td.name, "ckpt.hlidx")
        save_index(path, eng)
        eng = load_index(path)
        assert eng.name == backend
    return eng

GRAPHS = {
    "random": lambda: random_hypergraph(30, 45, seed=3),
    "chain": lambda: planted_chain_hypergraph(2, 6, overlap=2,
                                              extra_size=2, seed=0),
    "isolated": lambda: from_edge_lists([[0, 1, 2], [2, 3], [5, 6, 7],
                                         [6, 7, 8]], n=12),
}


def test_matrix_covers_registry_exactly():
    # the pinned tables and the live registry must agree both ways — a
    # backend registered without a row here (or vice versa) is loud
    assert set(EXPECTED_SNAPSHOT) == set(BACKENDS)
    assert set(EXPECTED_UPDATE) == set(BACKENDS)
    assert set(EXPECTED_WORKLOADS) == set(BACKENDS)
    assert update_capabilities() == EXPECTED_UPDATE
    assert workload_capabilities() == EXPECTED_WORKLOADS
    assert "vtv" not in BACKENDS          # unsound for MR (paper Example 5)


@pytest.fixture(scope="module", params=sorted(GRAPHS))
def case(request):
    h = GRAPHS[request.param]()
    rng = np.random.default_rng(7)
    us = rng.integers(0, h.n, 60)
    vs = rng.integers(0, h.n, 60)
    oracle = MSTOracle(h)
    want = np.array([oracle.mr(int(u), int(v)) for u, v in zip(us, vs)],
                    np.int64)
    return request.param, h, us, vs, want


_ENGINES = {}


def _engine(graph_name, h, config):
    """One engine per (graph, config), shared by the read-only ops."""
    key = (graph_name, config)
    if key not in _ENGINES:
        _ENGINES[key] = _build(h, config)
    return _ENGINES[key]


# ---------------------------------------------------------------------------
# the matrix: config × operation, answers pinned to mst-oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("config", CONFIG_NAMES)
def test_op_mr(case, config):
    name, h, us, vs, want = case
    eng = _engine(name, h, config)
    assert eng.name == CONFIGS[config][0]
    for u, v, w in zip(us[:20], vs[:20], want[:20]):
        assert eng.mr(int(u), int(v)) == int(w)
    # scalar paths reject out-of-range ids like the batch paths — a
    # Python negative index must never silently answer from another row
    with pytest.raises(IndexError):
        eng.mr(-1, 0)
    with pytest.raises(IndexError):
        eng.mr(0, h.n)


@pytest.mark.parametrize("config", CONFIG_NAMES)
def test_op_s_reach(case, config):
    name, h, us, vs, want = case
    eng = _engine(name, h, config)
    for s in (1, 2, 3):
        for u, v, w in zip(us[:10], vs[:10], want[:10]):
            assert eng.s_reach(int(u), int(v), s) == (int(w) >= s)


@pytest.mark.parametrize("config", CONFIG_NAMES)
def test_op_mr_batch(case, config):
    name, h, us, vs, want = case
    eng = _engine(name, h, config)
    got = np.asarray(eng.mr_batch(us, vs)).astype(np.int64)
    np.testing.assert_array_equal(got, want)
    assert len(eng.mr_batch([], [])) == 0     # empty batches legal


@pytest.mark.parametrize("config", CONFIG_NAMES)
def test_op_s_reach_batch(case, config):
    name, h, us, vs, want = case
    eng = _engine(name, h, config)
    for s in (1, 2, 3):
        got = np.asarray(eng.s_reach_batch(us, vs, s))
        np.testing.assert_array_equal(got, want >= s)


@pytest.mark.parametrize("config", CONFIG_NAMES)
def test_op_snapshot(case, config):
    name, h, us, vs, want = case
    eng = _engine(name, h, config)
    backend = CONFIGS[config][0]
    if not EXPECTED_SNAPSHOT[backend]:
        # capability asserted, not skipped: the declared-unsupported
        # backends must raise, and must keep raising (not silently grow
        # a half-working snapshot path)
        with pytest.raises(SnapshotUnsupported):
            eng.snapshot()
        return
    snap = eng.snapshot()
    got = np.asarray(snap.mr(us, vs)).astype(np.int64)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.asarray(snap.s_reach(us, vs, 2)),
                                  want >= 2)
    assert snap.backend == backend
    assert snap.version == eng.version
    assert snap.nbytes() > 0 or h.m == 0
    assert eng.snapshot() is snap             # cached while un-updated


@pytest.mark.parametrize("config", CONFIG_NAMES)
def test_op_update(case, config):
    name, h, us, vs, want = case
    backend, _ = CONFIGS[config]
    eng = _build(h, config)                   # fresh: update mutates
    assert eng.version == 0
    if EXPECTED_UPDATE[backend] == "unsupported":
        with pytest.raises(UpdateUnsupported):
            eng.update(inserts=[[0, 1]])
        assert eng.version == 0               # refused == untouched
        return
    ins, dels = [[0, 1, h.n - 1]], ([2] if h.m > 2 else [])
    eng.update(inserts=ins, deletes=dels)
    assert eng.version == 1
    h2, _, _ = apply_edge_edits(h, ins, dels)
    oracle = MSTOracle(h2)
    rng = np.random.default_rng(1)
    us2 = rng.integers(0, h2.n, 40)
    vs2 = rng.integers(0, h2.n, 40)
    want2 = np.array([oracle.mr(int(u), int(v)) for u, v in zip(us2, vs2)],
                     np.int64)
    got = np.asarray(eng.mr_batch(us2, vs2)).astype(np.int64)
    np.testing.assert_array_equal(got, want2)
    for u, v, w in zip(us2[:8], vs2[:8], want2[:8]):
        assert eng.mr(int(u), int(v)) == int(w)
        assert eng.s_reach(int(u), int(v), 2) == (int(w) >= 2)


# ---------------------------------------------------------------------------
# workload ops ride the same matrix: one row per op × config, answers
# pinned to the brute-force references; unsupported cells must raise
# WorkloadUnsupported (asserted, never skipped)
# ---------------------------------------------------------------------------

def _workload_supported(config, op):
    return EXPECTED_WORKLOADS[CONFIGS[config][0]][op]


@pytest.mark.parametrize("config", CONFIG_NAMES)
def test_op_witness(case, config):
    name, h, us, vs, want = case
    eng = _engine(name, h, config)
    if not _workload_supported(config, "witness"):
        with pytest.raises(WorkloadUnsupported):
            eng.mr_witness(int(us[0]), int(vs[0]))
        return
    for u, v, w in zip(us[:10], vs[:10], want[:10]):
        wit = eng.mr_witness(int(u), int(v))
        assert wit.u == int(u) and wit.v == int(v)
        assert wit.s == int(w)                # witness strength == MR
        assert verify_witness(h, wit)         # walk is a valid s-walk
    with pytest.raises(IndexError):
        eng.mr_witness(-1, 0)


@pytest.mark.parametrize("config", CONFIG_NAMES)
def test_op_s_reach_k(case, config):
    name, h, us, vs, want = case
    eng = _engine(name, h, config)
    if not _workload_supported(config, "s_reach_k"):
        with pytest.raises(WorkloadUnsupported):
            eng.s_reach_k(int(us[0]), int(vs[0]), 1, 1)
        return
    for s in (1, 2):
        for k in (1, 2, h.m):
            for u, v in zip(us[:8], vs[:8]):
                assert eng.s_reach_k(int(u), int(v), s, k) == \
                    brute_force_s_reach_k(h, int(u), int(v), s, k)
    with pytest.raises(ValueError):
        eng.s_reach_k(int(us[0]), int(vs[0]), 0, 1)
    with pytest.raises(ValueError):
        eng.s_reach_k(int(us[0]), int(vs[0]), 1, 0)


@pytest.mark.parametrize("config", CONFIG_NAMES)
def test_op_mr_set(case, config):
    name, h, us, vs, want = case
    eng = _engine(name, h, config)
    if not _workload_supported(config, "mr_set"):
        with pytest.raises(WorkloadUnsupported):
            eng.mr_set(us[:3], vs[:3])
        return
    for a, b in ((6, 6), (1, 12), (12, 1)):
        U, V = us[:a], vs[:b]
        assert eng.mr_set(U, V) == brute_force_mr_set(h, U, V)
    targets = np.arange(h.n)
    got = np.asarray(eng.mr_from_set(us[:5], targets)).astype(np.int64)
    np.testing.assert_array_equal(
        got, brute_force_mr_from_set(h, us[:5], targets))
    with pytest.raises(ValueError):
        eng.mr_set(np.array([], np.int64), vs[:3])


@pytest.mark.parametrize("config", CONFIG_NAMES)
def test_op_top_s(case, config):
    name, h, us, vs, want = case
    eng = _engine(name, h, config)
    if not _workload_supported(config, "top_s"):
        with pytest.raises(WorkloadUnsupported):
            eng.top_s(int(us[0]), 3)
        return
    for u in {int(x) for x in us[:6]}:
        for k in (1, 4, h.n):
            verts, vals = eng.top_s(u, k)
            bv, bs = brute_force_top_s(h, u, k)
            np.testing.assert_array_equal(np.asarray(verts), bv)
            np.testing.assert_array_equal(np.asarray(vals), bs)
    with pytest.raises(ValueError):
        eng.top_s(int(us[0]), 0)


@pytest.mark.parametrize("config", CONFIG_NAMES)
def test_op_s_distance(case, config):
    name, h, us, vs, want = case
    eng = _engine(name, h, config)
    if not _workload_supported(config, "s_distance"):
        with pytest.raises(WorkloadUnsupported):
            eng.s_distance(int(us[0]), int(vs[0]), 1)
        return
    for s in (1, 2):
        for u, v in zip(us[:12], vs[:12]):
            bound = eng.s_distance(int(u), int(v), s)
            exact = brute_force_s_distance(h, int(u), int(v), s)
            # certified: reachability is never wrong, bounds are walks
            assert (bound == 0) == (exact == 0), (u, v, s)
            assert bound >= exact
    with pytest.raises(ValueError):
        eng.s_distance(int(us[0]), int(vs[0]), 0)


# ---------------------------------------------------------------------------
# serving layer rides the same matrix: service answers == oracle on every
# backend (moved here from test_serving.py)
# ---------------------------------------------------------------------------

def _service(h, config):
    """A stopped service over one matrix row's engine."""
    backend, opts = CONFIGS[config]
    if opts.get("_restore"):
        return serve(_build(h, config), start=False)
    opts = dict(opts)
    svc_cfg = ServiceConfig(use_kernels=opts.pop("use_kernels", None))
    return serve(h, backend, start=False, config=svc_cfg, **opts)


@pytest.mark.parametrize("traffic", ["mixed_s", "uniform_s"])
@pytest.mark.parametrize("config", CONFIG_NAMES)
def test_service_matches_oracle(config, traffic):
    # uniform_s: every s-reach request carries one s, so a snapshot-less
    # backend answers the group through its own s_reach_batch
    h = random_hypergraph(30, 45, seed=3)
    svc = _service(h, config)
    oracle = MSTOracle(h)
    rng = np.random.default_rng(7)
    reqs, want = [], []
    for _ in range(80):
        u, v = int(rng.integers(h.n)), int(rng.integers(h.n))
        mr = oracle.mr(u, v)
        if rng.random() < 0.5:
            reqs.append(MRRequest(u, v))
            want.append(mr)
        else:
            s = 2 if traffic == "uniform_s" else int(rng.integers(1, 5))
            reqs.append(SReachRequest(u, v, s))
            want.append(mr >= s)
    futs = svc.submit_many(reqs)
    assert svc.pending() == 80
    svc.drain()
    assert svc.pending() == 0
    for req, fut, w in zip(reqs, futs, want):
        got = fut.result(timeout=0)
        assert got == w, (req, got, w)
        assert isinstance(got, int if req.kind == "mr" else bool)


# the join each matrix row's service answers a group by
# (reach_service._join_form)
EXPECTED_JOIN_FORM = {
    "closure": "aligned", "sharded": "aligned",
    "sharded[restored]": "aligned",
    "hl-index": "merge", "hl-index-basic": "merge", "ete": "merge",
    "hl-index[restored]": "merge", "hl-index[sharded-build]": "merge",
    "sharded[labels]": "merge",
    "hl-index[kernels]": "kernel", "sharded[kernels]": "kernel",
    "online": "engine", "frontier": "engine", "threshold": "engine",
    "mst-oracle": "engine",
}


@pytest.mark.parametrize("config", CONFIG_NAMES)
def test_service_counts_groups_by_join_form(config):
    # every group of a row's service takes the join its config implies
    h = random_hypergraph(40, 60, seed=9)
    oracle = mr_oracle_dense(h)
    svc = _service(h, config)
    rng = np.random.default_rng(4)
    pairs = rng.integers(0, h.n, (3, 2, 50))
    for us, vs in pairs:
        reqs = [MRRequest(int(u), int(v)) for u, v in zip(us, vs)]
        reqs += [SReachRequest(int(u), int(v), 2) for u, v in zip(us, vs)]
        futs = svc.submit_many(reqs)
        svc.drain()
        assert [f.result(timeout=0) for f in futs] == (
            [int(oracle[u, v]) for u, v in zip(us, vs)]
            + [bool(oracle[u, v] >= 2) for u, v in zip(us, vs)])
    st = svc.stats()
    assert st.batches >= 2
    assert st.join_form_groups == {EXPECTED_JOIN_FORM[config]: st.batches}
    assert st.as_dict()["join_form_groups"] == st.join_form_groups


# ---------------------------------------------------------------------------
# back-compat padded form (moved here from test_query_engines.py): the
# PaddedIndex constructor serves the same answers as the engine snapshot
# ---------------------------------------------------------------------------

def test_padded_index_backcompat_matches_oracle():
    h = random_hypergraph(40, 60, seed=9)
    idx = minimize(build_fast(h))
    pidx = PaddedIndex(idx)
    oracle = MSTOracle(h)
    rng = np.random.default_rng(0)
    us = rng.integers(0, h.n, 200)
    vs = rng.integers(0, h.n, 200)
    want = np.array([oracle.mr(int(u), int(v)) for u, v in zip(us, vs)],
                    np.int64)
    np.testing.assert_array_equal(np.asarray(pidx.mr(us, vs)).astype(np.int64),
                                  want)
    for s in (1, 2, 3):
        np.testing.assert_array_equal(np.asarray(pidx.s_reach(us, vs, s)),
                                      want >= s)
    snap = build_engine(h, "hl-index").snapshot()
    np.testing.assert_array_equal(np.asarray(pidx.mr(us, vs)),
                                  np.asarray(snap.mr(us, vs)))
