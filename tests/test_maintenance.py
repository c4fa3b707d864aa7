"""HL-index maintenance (paper Sec. V-D): scoped construction, spliced
labels, answers always identical to a full rebuild."""
import numpy as np
import pytest

from repro.core import (random_hypergraph, build_fast, minimize, mr_query,
                        mr_oracle_dense, insert_hyperedge, delete_hyperedge,
                        apply_updates, apply_edge_edits, from_edge_lists,
                        neighbor_csr, planted_chain_hypergraph)


def _assert_matches_oracle(idx, h):
    oracle = mr_oracle_dense(h)
    for u in range(h.n):
        for v in range(h.n):
            assert mr_query(idx, u, v) == int(oracle[u, v]), (u, v)


@pytest.mark.parametrize("seed", range(3))
def test_insert_matches_rebuild(seed):
    rng = np.random.default_rng(seed)
    h = random_hypergraph(20, 16, seed=seed)
    idx = build_fast(h)
    h2, idx2 = insert_hyperedge(h, idx, rng.choice(20, size=4, replace=False))
    _assert_matches_oracle(idx2, h2)


@pytest.mark.parametrize("seed", range(3))
def test_delete_matches_rebuild(seed):
    rng = np.random.default_rng(seed + 10)
    h = random_hypergraph(20, 16, seed=seed + 10)
    idx = build_fast(h)
    h2, idx2 = delete_hyperedge(h, idx, int(rng.integers(h.m)))
    _assert_matches_oracle(idx2, h2)


def test_insert_scope_is_component_local():
    # two disjoint chains: inserting into chain 0 must not touch chain 1's
    # hubs (scoped rebuild smaller than the graph)
    h = planted_chain_hypergraph(2, 10, overlap=2, extra_size=2, seed=0)
    idx = build_fast(h)
    v0 = int(h.edge(0)[0])
    h2, idx2 = insert_hyperedge(h, idx, [v0, v0 + 1])
    assert idx2.stats["maintenance_scope"] < h2.m
    oracle = mr_oracle_dense(h2)
    rng = np.random.default_rng(0)
    for _ in range(60):
        u, v = int(rng.integers(h2.n)), int(rng.integers(h2.n))
        assert mr_query(idx2, u, v) == int(oracle[u, v])


def test_construction_is_scoped():
    # the *construction* input is the extracted sub-hypergraph, not the
    # full graph, asserted on the stats the splice records
    h = planted_chain_hypergraph(4, 8, overlap=2, extra_size=2, seed=1)
    idx = build_fast(h)
    v0 = int(h.edge(0)[0])
    h2, idx2 = insert_hyperedge(h, idx, [v0, v0 + 1])
    assert 0 < idx2.stats["maintenance_subgraph_m"] < h2.m
    assert idx2.stats["maintenance_subgraph_m"] == \
        idx2.stats["maintenance_scope"]
    _assert_matches_oracle(idx2, h2)


def test_untouched_label_arrays_are_shared():
    # splice keeps out-of-scope vertices' label arrays byte-for-byte —
    # literally the same objects (insert-only edits don't even remap ids)
    h = planted_chain_hypergraph(2, 6, overlap=2, extra_size=2, seed=2)
    idx = build_fast(h)
    v0 = int(h.edge(0)[0])
    h2, idx2 = insert_hyperedge(h, idx, [v0, v0 + 1])
    chain1_edges = set(range(6, h.m))          # chain 1 = second component
    shared = 0
    for u in range(h.n):
        eu = set(int(e) for e in h.edges_of(u))
        if eu and eu <= chain1_edges:          # vertex wholly in chain 1
            assert idx2.labels_edge[u] is idx.labels_edge[u]
            assert idx2.labels_rank[u] is idx.labels_rank[u]
            assert idx2.labels_s[u] is idx.labels_s[u]
            shared += 1
    assert shared > 0


@pytest.mark.parametrize("use_minimizer", [False, True])
def test_batched_update_sequences_match_rebuild(use_minimizer):
    # randomized insert/delete batches; every step must answer exactly
    # like an index built from scratch on the edited graph
    rng = np.random.default_rng(42 + use_minimizer)
    h = random_hypergraph(16, 12, seed=7)
    idx = build_fast(h)
    minimizer = minimize if use_minimizer else None
    if use_minimizer:
        idx = minimize(idx)
    for step in range(6):
        ins, dels = [], []
        if h.m > 2 and rng.random() < 0.5:
            dels = list(rng.choice(h.m, size=int(rng.integers(1, 3)),
                                   replace=False))
        if rng.random() < 0.8:
            size = int(rng.integers(2, 5))
            ins.append(rng.choice(h.n + 2, size=min(size, h.n),
                                  replace=False))
        h, idx, report = apply_updates(h, idx, inserts=ins, deletes=dels,
                                       minimizer=minimizer)
        assert report.full_rebuild or report.scope <= h.m
        _assert_matches_oracle(idx, h)


def test_delete_isolated_hyperedge_clears_labels():
    h = from_edge_lists([[0, 1], [5, 6], [2, 3]], n=8)
    idx = build_fast(h)
    h2, idx2 = delete_hyperedge(h, idx, 1)     # isolated: no neighbors
    assert idx2.stats["maintenance_scope"] == 0
    assert idx2.labels_s[5].size == 0 and idx2.labels_s[6].size == 0
    _assert_matches_oracle(idx2, h2)


def test_delete_everything():
    h = from_edge_lists([[0, 1], [1, 2]], n=3)
    idx = build_fast(h)
    h2, idx2, _ = apply_updates(h, idx, deletes=[0, 1])
    assert h2.m == 0
    assert all(a.size == 0 for a in idx2.labels_s)
    assert mr_query(idx2, 0, 2) == 0


def test_insert_grows_vertex_set():
    h = from_edge_lists([[0, 1, 2]], n=3)
    idx = build_fast(h)
    h2, idx2 = insert_hyperedge(h, idx, [2, 7, 9])
    assert h2.n == 10
    _assert_matches_oracle(idx2, h2)


def test_insert_merging_components_invalidates_both():
    # a bridge hyperedge merges two chains: both become in-scope
    h = planted_chain_hypergraph(2, 5, overlap=2, extra_size=2, seed=3)
    idx = build_fast(h)
    u0 = int(h.edge(0)[0])                     # a chain-0 vertex
    u1 = int(h.edge(5)[0])                     # a chain-1 vertex
    h2, idx2 = insert_hyperedge(h, idx, [u0, u1])
    assert idx2.stats["maintenance_scope"] == h2.m   # everything merged
    _assert_matches_oracle(idx2, h2)


@pytest.mark.parametrize("seed", range(3))
def test_neighbor_csr_patch_matches_fresh(seed):
    # 1-hop CSR patching (satellite of the scoped-sharded PR): reusing
    # untouched rows through an edit must be byte-identical to paying
    # the full O(sum d^2) pair pass on the edited graph
    rng = np.random.default_rng(100 + seed)
    h = random_hypergraph(18, 14, seed=seed)
    csr = neighbor_csr(h)
    for _ in range(4):
        ins, dels = [], []
        if h.m > 2 and rng.random() < 0.6:
            dels = list(rng.choice(h.m, size=int(rng.integers(1, 3)),
                                   replace=False))
        if rng.random() < 0.8:
            size = int(rng.integers(2, 5))
            ins.append(rng.choice(h.n, size=min(size, h.n),
                                  replace=False))
        h2, old_to_new, touched = apply_edge_edits(h, inserts=ins,
                                                   deletes=dels)
        patched = csr.updated(h2, old_to_new, touched)
        fresh = neighbor_csr(h2)
        np.testing.assert_array_equal(patched.ptr, fresh.ptr)
        np.testing.assert_array_equal(patched.idx, fresh.idx)
        np.testing.assert_array_equal(patched.od, fresh.od)
        h, csr = h2, patched


def test_neighbor_csr_patch_to_empty_and_back():
    h = from_edge_lists([[0, 1], [1, 2]], n=3)
    csr = neighbor_csr(h)
    h2, old_to_new, touched = apply_edge_edits(h, deletes=[0, 1])
    csr2 = csr.updated(h2, old_to_new, touched)
    fresh2 = neighbor_csr(h2)
    np.testing.assert_array_equal(csr2.ptr, fresh2.ptr)
    h3, old_to_new, touched = apply_edge_edits(h2, inserts=[[0, 1, 2]])
    csr3 = csr2.updated(h3, old_to_new, touched)
    fresh3 = neighbor_csr(h3)
    np.testing.assert_array_equal(csr3.ptr, fresh3.ptr)
    np.testing.assert_array_equal(csr3.idx, fresh3.idx)
    np.testing.assert_array_equal(csr3.od, fresh3.od)


def test_apply_updates_threads_neighbor_csr():
    # apply_updates(neighbors=) must hand back a patched CSR equal to a
    # fresh one, and answers stay identical to the no-CSR path
    h = random_hypergraph(16, 12, seed=5)
    idx = build_fast(h)
    nbr = neighbor_csr(h)
    rng = np.random.default_rng(5)
    for _ in range(3):
        ins = [rng.choice(h.n, size=3, replace=False)]
        dels = [int(rng.integers(h.m))] if h.m > 1 else []
        h, idx, report = apply_updates(h, idx, inserts=ins, deletes=dels,
                                       neighbors=nbr)
        assert report.neighbors is not None
        nbr = report.neighbors
        fresh = neighbor_csr(h)
        np.testing.assert_array_equal(nbr.ptr, fresh.ptr)
        np.testing.assert_array_equal(nbr.idx, fresh.idx)
        _assert_matches_oracle(idx, h)
