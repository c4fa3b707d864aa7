"""Seeded hypergraphs with the shapes a configuration file states.

A configuration's ``hypergraph`` block gives the vertex and hyperedge
counts, the law of the hyperedge sizes and the law of the vertex degrees:

    {"n": 88860, "m": 69906,
     "sizes": {"law": "max_entropy", "min": 2, "max": 25, "mean": 6.59},
     "degrees": {"law": "power", "top_share": 0.082},
     "unique_edges": true}

Sizes:

* ``counts``: the published histogram, ``{"2": 5498, "3": 2091, ...}``;
  the counts must add up to ``m``;
* ``max_entropy``: the law of greatest entropy on ``min..max`` with the
  published mean (a truncated geometric), where only the mean and the
  range are published.

Every seed gets the same hypergraph up to names: its structure is drawn
from one fixed stream, and the run's seed only renames the vertices and
reorders the hyperedges.  So
every seed brings the same sizes, the same degrees and the same index
shapes (label widths), and with them the same work, in another order.

Degrees: ``uniform`` (every vertex alike), or ``power``: vertex of rank
``i`` is drawn with weight ``i ** -alpha``, with ``alpha`` chosen so that
the top vertex is expected in ``top_share`` of the hyperedges (a share,
so that it holds when ``n`` and ``m`` are cut alike; the mean degree is
fixed by the sizes).  The renaming makes which vertices are hot differ
by seed; how hot does not.

Each hyperedge holds distinct vertices; with ``unique_edges`` no two
hyperedges hold the same vertex set.  The draw is vectorised and fixed
by the seed alone.  The program under test and the reference both
receive these edge lists; neither sees the other's structures.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

__all__ = ["make_edges", "edge_lists", "size_counts", "degree_weights"]

_STRUCTURE_STREAM = [0, 0x4859]     # the one stream every structure is drawn from


def _bisect(f, lo: float, hi: float, iters: int = 200) -> float:
    """The root of increasing ``f`` on ``[lo, hi]``."""
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        if f(mid) < 0:
            lo = mid
        else:
            hi = mid
    return 0.5 * (lo + hi)


def _round_to(p: np.ndarray, total: int) -> np.ndarray:
    """Counts proportional to ``p`` that add up to ``total`` (largest
    remainder)."""
    raw = p / p.sum() * total
    counts = np.floor(raw).astype(np.int64)
    short = total - int(counts.sum())
    counts[np.argsort(counts - raw, kind="stable")[:short]] += 1
    return counts


def size_counts(law: dict, m: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(sizes, counts)``: how many of the ``m`` hyperedges have each
    size, the same for every seed."""
    if law["law"] == "counts":
        sizes = np.array(sorted(int(k) for k in law["counts"]), np.int64)
        counts = np.array([int(law["counts"][str(k)]) for k in sizes],
                          np.int64)
        if counts.sum() != m:
            raise ValueError(f"size counts add up to {counts.sum()}, "
                             f"not m = {m}")
        return sizes, counts
    if law["law"] == "max_entropy":
        lo, hi, mean = int(law["min"]), int(law["max"]), float(law["mean"])
        if not lo < mean < hi:
            raise ValueError(f"need min < mean < max; got {lo}, {mean}, "
                             f"{hi}")
        sizes = np.arange(lo, hi + 1)
        k = (sizes - lo) / (hi - lo)

        def law_mean(lam):
            w = np.exp(-lam * k)
            return float((w * sizes).sum() / w.sum())
        lam = _bisect(lambda x: mean - law_mean(x), -200.0, 200.0)
        return sizes, _round_to(np.exp(-lam * k), m)
    raise ValueError(f"unknown size law {law!r}")


def degree_weights(law: dict, n: int, m: int,
                   incidences: int) -> np.ndarray:
    """Draw weight of the vertex of each rank (descending), summing to 1."""
    if law["law"] == "uniform":
        return np.full(n, 1.0 / n)
    if law["law"] == "power":
        top = float(law["top_share"]) * m
        if not incidences / n < top <= m:
            raise ValueError(f"the top vertex's degree {top:.1f} must lie "
                             f"above the mean {incidences / n:.3f} and "
                             f"within m = {m}")
        rank = np.arange(1, n + 1, dtype=np.float64)
        alpha = _bisect(lambda a: incidences / np.sum(rank ** -a) - top,
                        0.0, 20.0)
        w = rank ** -alpha
        return w / w.sum()
    raise ValueError(f"unknown degree law {law!r}")


def _rows_with_repeats(rows: np.ndarray, sizes: np.ndarray) -> np.ndarray:
    """Indices of rows whose first ``size`` entries repeat a vertex."""
    width = rows.shape[1]
    pad = np.where(np.arange(width)[None, :] < sizes[:, None], rows,
                   -1 - np.arange(width)[None, :])   # distinct negatives
    s = np.sort(pad, axis=1)
    return np.nonzero((s[:, 1:] == s[:, :-1]).any(axis=1))[0]


def _duplicate_rows(rows: np.ndarray, sizes: np.ndarray) -> np.ndarray:
    """Indices of rows whose vertex set equals an earlier row's."""
    width = rows.shape[1]
    canon = np.sort(np.where(np.arange(width)[None, :] < sizes[:, None],
                             rows, -1), axis=1)
    _, first = np.unique(canon, axis=0, return_index=True)
    dup = np.ones(len(rows), bool)
    dup[first] = False
    return np.nonzero(dup)[0]


def make_edges(spec: dict, seed: int) -> Tuple[int, np.ndarray, np.ndarray]:
    """Return ``(n, e_ptr, e_idx)``: hyperedge ``e`` holds the vertices
    ``e_idx[e_ptr[e]:e_ptr[e + 1]]``, sorted ascending."""
    n, m = int(spec["n"]), int(spec["m"])
    size_of, count = size_counts(spec["sizes"], m)
    hi = int(size_of.max())
    if size_of.min() < 1 or hi > n:
        raise ValueError(f"hyperedge sizes must lie in 1..n = {n}")
    rng = np.random.default_rng(_STRUCTURE_STREAM)
    sizes = rng.permutation(np.repeat(size_of, count))
    weights = degree_weights(spec["degrees"], n, m, int(sizes.sum()))
    cdf = np.cumsum(weights)

    def draw(k):
        r = np.searchsorted(cdf, rng.random((k, hi)) * cdf[-1],
                            side="right")
        return np.minimum(r, n - 1)

    rows = draw(m)
    unique = bool(spec.get("unique_edges", False))
    for _ in range(1000):
        bad = _rows_with_repeats(rows, sizes)
        if unique and bad.size == 0:
            bad = _duplicate_rows(rows, sizes)
        if bad.size == 0:
            break
        rows[bad] = draw(bad.size)
    else:
        raise RuntimeError("could not draw distinct hyperedges; the "
                           "configuration asks for more than n allows")
    # the seed names the vertices and orders the hyperedges
    names = np.random.default_rng([int(seed), 0x4859])
    name_of = names.permutation(n)
    order = names.permutation(m)
    rows, sizes = name_of[rows[order]], sizes[order]
    keep = np.arange(hi)[None, :] < sizes[:, None]
    rows = np.sort(np.where(keep, rows, n), axis=1)   # pads sort last
    e_idx = rows[keep]
    e_ptr = np.zeros(m + 1, np.int64)
    np.cumsum(sizes, out=e_ptr[1:])
    return n, e_ptr, e_idx.astype(np.int64)


def edge_lists(e_ptr: np.ndarray, e_idx: np.ndarray) -> List[np.ndarray]:
    """The hyperedges as a list of vertex arrays (what the program's
    ``from_edge_lists`` takes)."""
    return np.split(e_idx, e_ptr[1:-1])
