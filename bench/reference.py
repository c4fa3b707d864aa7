"""Plain reference for max-reachability, written from the definition.

An s-walk is a sequence of hyperedges, each of at least s vertices, in
which consecutive hyperedges share at least s vertices.  MR(u, v) is the
largest s for which some s-walk starts at a hyperedge holding u and ends
at one holding v (one hyperedge holding both is a walk of length one, so
MR(u, u) is the size of u's largest hyperedge), and 0 when there is none.
s-reach(u, v, s) is MR(u, v) >= s.

So for each s from the largest hyperedge size down to 1, this module
takes the graph whose nodes are the hyperedges of size >= s and whose
links join hyperedges that share >= s vertices, finds its connected
components, and answers MR(u, v) = s for every still-open query whose
endpoints touch a common component.  It uses numpy and scipy only and
imports nothing of the program under test; it is independent of the
HL-index labels, the (max, min) closure and the program's own
``mst-oracle`` alike.

``control`` is the reference with the exactness guarantee broken: MR
rounded down to the coarse threshold ladder {1, 2, 4, 8, ...}, the
answer a bucketised closure (``threshold_closure_mr`` given a coarse
ladder) would give.  A run whose answers are the control's must read as
not correct.
"""
from __future__ import annotations

import numpy as np
from scipy.sparse import coo_matrix, csr_matrix
from scipy.sparse.csgraph import connected_components

__all__ = ["max_reach", "answers", "control"]


def _overlaps(n: int, e_ptr: np.ndarray, e_idx: np.ndarray):
    """(a, b, shared) for every pair a < b of hyperedges sharing a vertex."""
    m = len(e_ptr) - 1
    sizes = np.diff(e_ptr)
    edge_of = np.repeat(np.arange(m), sizes)
    inc = csr_matrix((np.ones(e_idx.size, np.int32), (e_idx, edge_of)),
                     shape=(n, m))
    shared = (inc.T @ inc).tocoo()
    upper = shared.row < shared.col
    return shared.row[upper], shared.col[upper], shared.data[upper]


def _touch_common(us, vs, vertex, comp, ncomp, n):
    """For each query, whether u and v lie on hyperedges of one component.
    ``vertex``/``comp`` list the (vertex, component) incidences."""
    keys = np.unique(vertex.astype(np.int64) * ncomp + comp)
    owner = keys // ncomp
    start = np.searchsorted(owner, np.arange(n + 1))
    count = np.diff(start)
    a = np.where(count[us] <= count[vs], us, vs)     # the side with fewer
    b = np.where(count[us] <= count[vs], vs, us)
    per = count[a]
    total = int(per.sum())
    if total == 0:
        return np.zeros(us.size, bool)
    query = np.repeat(np.arange(us.size), per)
    offset = np.arange(total) - np.repeat(np.cumsum(per) - per, per)
    c = keys[start[a][query] + offset] % ncomp
    want = b[query].astype(np.int64) * ncomp + c
    pos = np.minimum(np.searchsorted(keys, want), keys.size - 1)
    hit = keys[pos] == want
    return np.bincount(query[hit], minlength=us.size) > 0


def max_reach(n: int, e_ptr, e_idx, us, vs) -> np.ndarray:
    """MR(us[i], vs[i]) for every query, as int64."""
    e_ptr = np.asarray(e_ptr, np.int64)
    e_idx = np.asarray(e_idx, np.int64)
    us = np.asarray(us, np.int64)
    vs = np.asarray(vs, np.int64)
    m = len(e_ptr) - 1
    out = np.zeros(us.size, np.int64)
    if m == 0 or us.size == 0:
        return out
    sizes = np.diff(e_ptr)
    edge_of = np.repeat(np.arange(m), sizes)
    a, b, shared = _overlaps(n, e_ptr, e_idx)
    open_q = np.arange(us.size)
    for s in range(int(sizes.max()), 0, -1):
        if open_q.size == 0:
            break
        link = shared >= s
        graph = coo_matrix((np.ones(int(link.sum()), np.int8),
                            (a[link], b[link])), shape=(m, m))
        ncomp, comp = connected_components(graph, directed=False)
        live = sizes[edge_of] >= s
        hit = _touch_common(us[open_q], vs[open_q], e_idx[live],
                            comp[edge_of[live]], ncomp, n)
        out[open_q[hit]] = s
        open_q = open_q[~hit]
    return out


def answers(mr: np.ndarray, is_mr: np.ndarray, s: np.ndarray) -> np.ndarray:
    """The expected answer of each request: MR itself for an MR request,
    1 or 0 for an s-reach request."""
    return np.where(is_mr, mr, (mr >= s).astype(np.int64))


def control(mr: np.ndarray) -> np.ndarray:
    """MR rounded down to the ladder {0, 1, 2, 4, 8, ...}."""
    mr = np.asarray(mr, np.int64)
    ladder = np.zeros_like(mr)
    pos = mr > 0
    ladder[pos] = 1 << (np.floor(np.log2(mr[pos]))).astype(np.int64)
    return ladder
