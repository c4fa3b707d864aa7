"""The ``closure`` backend's vertex rows, built on the device by (max, min)
propagation (``semiring.vertex_rows``): exact against ``mst-oracle`` and
the brute force, byte-equal to the segment-max of the whole closure W*,
with the rounds to the fixpoint recorded; the planner's route to it; and
its stores, old and new."""
import jax
import numpy as np
import pytest

from repro.api import (ServiceConfig, build_engine, from_edge_lists,
                       plan_backend, random_hypergraph, serve)
from repro.core import baselines
from repro.core.engine import (CLOSURE_BUILD_BUDGET, CLOSURE_JOIN_BATCH,
                               ClosureEngine, SINGLE_DEVICE_CLOSURE_BUDGET,
                               closure_build_pairs,
                               closure_working_set_bytes,
                               device_memory_budget)
from repro.core.hypergraph import Hypergraph
from repro.core.semiring import edge_mr_rows, mr_matrix, vertex_rows
from repro.store import format as store_format
from repro.store.format import load_index, save_index


def email_like(n: int, m: int, seed: int, top: float = 0.8):
    """Emails as hyperedges: mostly 2-4 members drawn by power-law weights
    (a few members in a large share of the emails), so each vertex lies
    on many hyperedges and the overlaps are high."""
    rng = np.random.default_rng(seed)
    p = np.arange(1, n + 1, dtype=float) ** -top
    p /= p.sum()
    sizes = rng.choice([2, 2, 2, 3, 3, 4, 5, 8], size=m)
    return from_edge_lists([rng.choice(n, size=int(k), replace=False, p=p)
                            for k in sizes], n=n)


def chain(length: int) -> Hypergraph:
    """Hyperedges {i, i+1}: the optimal walk from vertex 0 to the last
    hyperedge takes ``length - 1`` hops."""
    return from_edge_lists([[i, i + 1] for i in range(length)],
                           n=length + 2)      # the last vertex is isolated


GRAPHS = {
    "sparse": lambda: random_hypergraph(40, 60, seed=4),
    "wide_edges": lambda: random_hypergraph(25, 30, max_size=9, seed=7),
    "isolated_vertices": lambda: random_hypergraph(50, 12, seed=2),
    "email_like": lambda: email_like(30, 400, seed=3),
    "chain": lambda: chain(9),
    "no_edges": lambda: from_edge_lists([], n=5),
}


@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_rows_byte_equal_the_segment_max_of_the_closure(graph):
    h = GRAPHS[graph]()
    rows, rounds = vertex_rows(h)
    got = np.asarray(rows)
    want = edge_mr_rows(h, mr_matrix(h))
    assert got.dtype == want.dtype == np.int32
    assert got.shape == (h.n, h.m)
    assert got.tobytes() == want.tobytes()
    assert 0 <= rounds <= h.m


@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_closure_engine_answers_exactly(graph):
    h = GRAPHS[graph]()
    eng = build_engine(h, "closure")
    oracle = baselines.MSTOracle(h)
    us, vs = np.divmod(np.arange(h.n * h.n), h.n)
    want = np.array([oracle.mr(int(u), int(v)) for u, v in zip(us, vs)])
    assert np.array_equal(eng.mr_batch(us, vs), want)
    assert np.array_equal(eng.s_reach_batch(us, vs, 2), want >= 2)
    # scalar lookups read the rows on the host; the brute force is an
    # independent threshold sweep
    pick = np.random.default_rng(0).choice(us.size, 25, replace=False)
    for q in pick:
        u, v = int(us[q]), int(vs[q])
        assert eng.mr(u, v) == want[q]
        assert baselines.brute_force_witness(h, u, v)[0] == want[q]


@pytest.mark.parametrize("length", [1, 2, 5, 12])
def test_rounds_are_the_longest_walk_plus_one(length):
    # round i adds the walks of i hops; the round after the longest
    # optimal walk changes nothing and ends the loop
    eng = build_engine(chain(length), "closure")
    assert eng.rounds == length


def test_closure_build_makes_no_square_on_the_host(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("the closure build made the [m, m] overlap "
                             "matrix on the host")

    monkeypatch.setattr(Hypergraph, "line_graph", refuse)
    h = email_like(12, 90, seed=5)
    eng = build_engine(h, "closure")
    assert isinstance(eng.rows, jax.Array) and eng.rows.shape == (h.n, h.m)
    assert not hasattr(eng, "w_star")
    for value in vars(eng).values():
        assert getattr(value, "shape", None) != (h.m, h.m)
    # the answers, the snapshot and an update read or rebuild the rows
    eng.snapshot()
    assert eng.mr(0, 1) == baselines.MSTOracle(h).mr(0, 1)
    eng.update(inserts=[[0, 1, 2]])
    assert eng.rows.shape == (h.n, h.m + 1)
    assert eng.mr(0, 2) >= 3


# -- the planner ------------------------------------------------------------

def dense_email():
    """Label mass (nnz x mean degree) over the 2e6 budget; the closure's
    working set (76 MiB at a 256 join) under the CPU's fallback budget."""
    return email_like(60, 3500, seed=11, top=0.5)


def test_working_set_counts_overlap_rows_and_one_join():
    n, m, q = 998, 25_800, 4096
    assert closure_working_set_bytes(n, m, q) == (
        4 * m * m + 2 * 4 * n * m + 4 * 4 * q * 2 * m)


def test_device_memory_budget_reads_the_device(monkeypatch):
    # the CPU reports no memory: the fallback constant
    assert jax.devices()[0].memory_stats() is None
    assert device_memory_budget() == SINGLE_DEVICE_CLOSURE_BUDGET

    class Chip:
        def memory_stats(self):
            return {"bytes_limit": 16 * 2**30, "bytes_in_use": 0}

    monkeypatch.setattr(jax, "devices", lambda: [Chip()])
    assert device_memory_budget() == 12 * 2**30


def test_build_estimate_is_rounds_of_n_m_squared():
    # ceil(log2 m) hops and the round that changes nothing
    assert closure_build_pairs(998, 25_800) == 998 * 25_800 ** 2 * 16
    assert closure_build_pairs(327, 7818) == 327 * 7818 ** 2 * 14
    assert closure_build_pairs(3, 1) == 3 * 2


def strided(n: int, sizes) -> Hypergraph:
    """Hyperedge i holds vertices i, i + 331, i + 662, ... (mod n): every
    vertex on about the same number of hyperedges."""
    return from_edge_lists([(i + 331 * np.arange(k)) % n
                            for i, k in enumerate(sizes)], n=n)


@pytest.mark.parametrize("shape, hint, want", [
    ("email_eu", None, "closure"),
    ("email_eu", 4096, "closure"),
    ("square", None, "online"),
    ("square", 4096, "frontier"),
])
def test_planner_weighs_the_rows_build(shape, hint, want):
    # both fit a 16-GiB chip's budget; the n = m graph would pay m³ a
    # round for up to m rounds, so it stays index-free
    if shape == "email_eu":           # 998 x 25,800, mean degree 85
        h = strided(998, 3 + (np.arange(25_800) % 1000 < 288))
    else:                             # 20,000 x 20,000, mean degree 20
        h = strided(20_000, np.full(20_000, 20))
    budget = 12 * 2**30
    assert h.nnz * h.vertex_degrees.mean() > 2e6
    assert closure_working_set_bytes(h.n, h.m, CLOSURE_JOIN_BATCH) <= budget
    assert ((closure_build_pairs(h.n, h.m) <= CLOSURE_BUILD_BUDGET)
            == (want == "closure"))
    assert plan_backend(h, hint, device_budget_bytes=budget) == want


@pytest.mark.parametrize("hint, budget, join, want", [
    (None, None, 256, "closure"),          # the CPU's fallback budget
    (512, None, 256, "closure"),
    (None, None, None, "online"),          # a 4,096 join does not fit it
    (512, 2**20, 256, "frontier"),
    (None, 2**40, None, "closure"),
])
def test_planner_routes_dense_graphs_to_closure(hint, budget, join, want):
    h = dense_email()
    assert h.nnz * h.vertex_degrees.mean() > 2e6
    assert plan_backend(h, hint, device_budget_bytes=budget,
                        join_batch=join) == want


def test_planner_reserves_the_join_it_is_told_of():
    h = dense_email()
    fits = closure_working_set_bytes(h.n, h.m, 256)
    assert plan_backend(h, device_budget_bytes=fits,
                        join_batch=256) == "closure"
    assert plan_backend(h, device_budget_bytes=fits) == "online"
    assert fits < closure_working_set_bytes(h.n, h.m, CLOSURE_JOIN_BATCH)


@pytest.mark.parametrize("budget", [None, 2**40])
def test_walmart_like_graphs_still_route_to_hl_index(budget):
    # co-purchase baskets: sizes up to 25, a low mean degree, so the
    # label build is cheap whatever the device holds
    h = random_hypergraph(2000, 1600, max_size=25, seed=8)
    assert h.nnz * h.vertex_degrees.mean() <= 2e6
    assert plan_backend(h, device_budget_bytes=budget) == "hl-index"
    assert plan_backend(h, 4096, device_budget_bytes=budget) == "hl-index"


def test_serve_auto_builds_the_rows_for_a_dense_graph():
    h = dense_email()
    svc = serve(h, config=ServiceConfig(max_batch=256), start=False)
    assert isinstance(svc.engine, ClosureEngine)
    assert svc.engine.rounds >= 1
    rng = np.random.default_rng(1)
    us, vs = rng.integers(0, h.n, 64), rng.integers(0, h.n, 64)
    oracle = baselines.MSTOracle(h)
    want = [oracle.mr(int(u), int(v)) for u, v in zip(us, vs)]
    assert svc.engine.mr_batch(us, vs).tolist() == want
    svc.close()


# -- stores -------------------------------------------------------------------

@pytest.mark.parametrize("payload", ["rows", "w_star"])
def test_closure_store_round_trip(tmp_path, payload):
    h = email_like(20, 120, seed=6)
    eng = build_engine(h, "closure")
    path = tmp_path / "closure.hlx"
    if payload == "rows":
        save_index(path, eng)
    else:
        # a store written before the rows were saved: the whole closure
        meta = {"backend": "closure", "engine_version": 0, "n": h.n,
                "m": h.m, "payload": "closure",
                "engine_opts": {"method": "maxmin"}}
        store_format._write_store_file(path, meta, [
            ("h.e_ptr", h.e_ptr), ("h.e_idx", h.e_idx),
            ("h.v_ptr", h.v_ptr), ("h.v_idx", h.v_idx),
            ("w_star", mr_matrix(h))])
    back = load_index(path, expect_backend="closure")
    got = np.asarray(back.rows)
    assert got.dtype == np.int32
    assert got.tobytes() == np.asarray(eng.rows).tobytes()
    us, vs = np.divmod(np.arange(h.n * h.n), h.n)
    assert np.array_equal(back.mr_batch(us, vs), eng.mr_batch(us, vs))
    if payload == "w_star":
        # saved again, it is written in the rows' form
        save_index(tmp_path / "again.hlx", back)
        _, seg = store_format.load_segments(tmp_path / "again.hlx")
        assert "rows" in seg and "w_star" not in seg
