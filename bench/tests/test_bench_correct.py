"""The check that decides ``correct``: the reference against a brute force,
and whole runs of the harness at a size a test run holds, on the CPU,
with the chip check skipped: a sound run reads correct; the reference's
control and each fault planted in the timed path read not correct."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import harness  # noqa: E402
import hypergraphs  # noqa: E402
import manifest  # noqa: E402
import reference  # noqa: E402

SPEC = manifest.load(ROOT)
# each configuration at a size a test run holds: fewer vertices and
# hyperedges, a smaller largest bucket, a low rate or few in flight;
# the shapes of the configuration otherwise
SMALL_GRAPH = {"walmart-trips": {"n": 2000, "m": 1600},
               "contact-high-school": {
                   "n": 40, "m": 300, "sizes": {"law": "counts", "counts": {
                       "2": 200, "3": 80, "4": 15, "5": 5}},
                   "degrees": {"law": "power", "top_share": 0.15}}}
SMALL_TRAFFIC = {"open": {"arrivals": {"process": "poisson",
                                       "rate_rps": 1500}},
                 "closed": {"outstanding": 128}}
CELLS = sorted(w["name"] for w in SPEC["workloads"])


def brute_force_mr(n, e_ptr, e_idx):
    """All-pairs MR by Floyd-Warshall on the (max, min) semiring."""
    edges = [set(e_idx[e_ptr[i]:e_ptr[i + 1]].tolist())
             for i in range(len(e_ptr) - 1)]
    m = len(edges)
    w = np.zeros((m, m), np.int64)
    for i in range(m):
        for j in range(m):
            w[i, j] = len(edges[i] & edges[j])
    for k in range(m):
        w = np.maximum(w, np.minimum(w[:, k:k + 1], w[k:k + 1, :]))
    out = np.zeros((n, n), np.int64)
    for u in range(n):
        for v in range(n):
            eu = [i for i in range(m) if u in edges[i]]
            ev = [i for i in range(m) if v in edges[i]]
            if eu and ev:
                out[u, v] = w[np.ix_(eu, ev)].max()
    return out


@pytest.mark.parametrize("n,m,lo,hi,degrees,seed", [
    (12, 10, 2, 4, {"law": "uniform"}, 0),
    (30, 25, 2, 6, {"law": "power", "top_share": 0.3}, 1),
    (15, 40, 2, 5, {"law": "power", "top_share": 0.4}, 2 ** 31 + 9)])
def test_reference_matches_brute_force(n, m, lo, hi, degrees, seed):
    _, e_ptr, e_idx = hypergraphs.make_edges(
        {"n": n, "m": m, "sizes": {"law": "max_entropy", "min": lo,
                                   "max": hi, "mean": (lo + hi) / 2},
         "degrees": degrees, "unique_edges": True}, seed)
    want = brute_force_mr(n, e_ptr, e_idx)
    us, vs = np.divmod(np.arange(n * n), n)
    got = reference.max_reach(n, e_ptr, e_idx, us, vs)
    assert np.array_equal(got, want[us, vs])
    assert len(np.unique(got)) >= 3             # the check is not trivial


def test_generated_hypergraph_has_the_stated_shape():
    """contact-high-school as configured: the published size histogram
    exactly, on every seed, and the stated skew of degrees."""
    spec = json.loads((ROOT / "bench/configs/contact-high-school.json")
                      .read_text())["hypergraph"]
    n, e_ptr, e_idx = hypergraphs.make_edges(spec, 2 ** 31 + 77)
    sizes = np.diff(e_ptr)
    assert n == 327 and len(sizes) == 7818
    assert np.bincount(sizes).tolist() == [0, 0, 5498, 2091, 222, 7]
    rows = {tuple(e_idx[a:b]) for a, b in zip(e_ptr[:-1], e_ptr[1:])}
    assert len(rows) == 7818                          # unique hyperedges
    assert all(len(set(r)) == len(r) for r in rows)   # distinct vertices
    degree = np.bincount(e_idx, minlength=n)
    assert degree.mean() == pytest.approx(18192 / 327)
    # the top student near 1.78% of the groups (139), the rest spread
    assert 110 < degree.max() <= 139 and degree.min() > 0
    _, p2, i2 = hypergraphs.make_edges(spec, 2 ** 31 + 77)
    assert np.array_equal(p2, e_ptr) and np.array_equal(i2, e_idx)
    _, p3, i3 = hypergraphs.make_edges(spec, 5)
    assert np.array_equal(p3[-1], e_ptr[-1])            # same sizes ...
    assert not np.array_equal(i3, e_idx)                # ... new names
    # ... and the same graph under them: sizes and degrees alike
    assert np.array_equal(np.sort(np.diff(p3)), np.sort(sizes))
    assert np.array_equal(np.sort(np.bincount(i3, minlength=n)),
                          np.sort(degree))


def test_max_entropy_sizes_keep_the_published_mean_and_range():
    """walmart-trips as configured: sizes on 2..25 with mean 6.59, the
    same multiset on every seed; the top product in about 8.2% of the
    baskets."""
    spec = json.loads((ROOT / "bench/configs/walmart-trips.json")
                      .read_text())["hypergraph"]
    sizes, counts = hypergraphs.size_counts(spec["sizes"], spec["m"])
    assert sizes.min() == 2 and sizes.max() == 25
    assert counts.sum() == spec["m"] and counts.min() > 0
    assert np.all(np.diff(counts) < 0)               # falls with size
    assert (sizes * counts).sum() / spec["m"] == pytest.approx(6.59,
                                                               abs=0.005)
    n, e_ptr, e_idx = hypergraphs.make_edges(spec, 2 ** 31 + 1)
    degree = np.bincount(e_idx, minlength=n)
    assert 0.06 * spec["m"] < degree.max() <= 0.082 * spec["m"]
    assert degree.mean() == pytest.approx(6.59 * spec["m"] / n, rel=0.01)


def test_size_and_degree_laws_refuse_what_they_cannot_draw():
    with pytest.raises(ValueError):
        hypergraphs.size_counts({"law": "counts", "counts": {"2": 3}}, 4)
    with pytest.raises(ValueError):
        hypergraphs.size_counts({"law": "max_entropy", "min": 2,
                                 "max": 5, "mean": 6}, 10)
    with pytest.raises(ValueError):
        hypergraphs.degree_weights({"law": "power", "top_share": 0.001},
                                   100, 50, 200)
    with pytest.raises(ValueError):
        hypergraphs.size_counts({"law": "zipf"}, 10)


def test_control_rounds_down_to_the_ladder():
    mr = np.arange(10)
    assert reference.control(mr).tolist() == [0, 1, 2, 2, 4, 4, 4, 4, 8, 8]


def small_cell(name):
    c = manifest.cell(SPEC, name, ROOT)
    c.config["hypergraph"].update(SMALL_GRAPH[c.config_name])
    c.config["service"].update(max_batch=256)
    c.traffic.update(SMALL_TRAFFIC[c.traffic["loop"]])
    return c


def run_small(name, **kw):
    import time
    return harness.run(small_cell(name), seed=2 ** 31 + 3, seconds=1.0,
                       trace=False, t_start=time.perf_counter(),
                       work_dir=ROOT / ".bench_out", **kw)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_reads_correct(name):
    r = run_small(name)
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] > 100
    assert list(r)[-1] == "checks"
    assert r["checks"]["mismatched"] == {"value": 0, "limit": 0}
    assert r["window"]["compiles_in_window"] == 0
    names = {m["name"] for m in manifest.cell(SPEC, name, ROOT).end_to_end}
    assert set(r["metrics"]) == names
    assert all(v["value"] > 0 for v in r["metrics"].values())


@pytest.mark.parametrize("name", CELLS)
def test_control_reads_not_correct(name):
    r = run_small(name, control="ladder")
    assert r["correct"] is False
    assert r["checks"]["mismatched"]["value"] > 0


def _answer_altered(orig):
    def join(ranks, svals, us, vs):
        return orig(ranks, svals, us, vs).at[0].add(1)
    return join


def _half_left_out(orig):
    def join(ranks, svals, us, vs):
        out = orig(ranks, svals, us, vs)
        half = out.shape[0] // 2
        return out.at[half:].set(0) if half else out.at[:].set(0)
    return join


@pytest.mark.parametrize("fault", [_answer_altered, _half_left_out],
                         ids=["answer_altered", "half_batch_left_out"])
@pytest.mark.parametrize("name", CELLS)
def test_fault_in_the_timed_path_reads_not_correct(name, fault,
                                                   monkeypatch):
    from repro.core import query
    monkeypatch.setattr(query, "batched_mr", fault(query.batched_mr))
    r = run_small(name)
    assert r["correct"] is False
    assert r["checks"]["mismatched"]["value"] > 0


def test_closed_loop_closes_when_all_it_sent_is_answered():
    """A closed loop's rate counts every answer after the window opened,
    over the time to the last one; an open loop's, the answers inside
    the window over the window."""
    import loadgen
    z = np.zeros(4, np.int64)
    done = np.array([9.5, 10.2, 10.9, 11.3])
    log = loadgen.Log(10.0, 1.0, z.astype(bool), z, z, z, done - 0.1,
                      done - 0.1, done, np.full(4, loadgen.INT, np.int8),
                      z)
    served, served_s = harness._served(log, "closed")
    assert served == 3 and served_s == pytest.approx(1.3)
    assert harness._served(log, "open") == (2, 1.0)
