"""Query kernels: the Pallas label-join and the XLA joins (``batched_mr``
and, on key-aligned rows, ``batched_mr_aligned``) vs the merge-join
reference.

The padded-engine-vs-oracle equivalence checks that used to live here
are conformance matrix cells now (tests/test_conformance.py: the
``snapshot`` operation and the PaddedIndex back-compat test); this file
keeps the kernel-specific coverage.
"""
import json
import types

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (random_hypergraph, build_fast, minimize,
                        PaddedIndex, mr_oracle_dense, batched_mr, mr_query)
from repro.core.hlindex import pad_label_rows
from repro.core.query import DeviceSnapshot, batched_mr_aligned
from repro.kernels import label_join, MAX_RANK

from util_subproc import run_with_devices


@pytest.fixture(scope="module")
def setup():
    h = random_hypergraph(40, 60, seed=9)
    idx = minimize(build_fast(h))
    oracle = mr_oracle_dense(h)
    return h, idx, oracle


def test_pallas_label_join_matches_batched(setup):
    h, idx, oracle = setup
    ranks, svals, _ = idx.as_padded()
    rng = np.random.default_rng(2)
    us = rng.integers(0, h.n, 64)
    vs = rng.integers(0, h.n, 64)
    got = np.asarray(label_join(jnp.asarray(ranks[us]), jnp.asarray(svals[us]),
                                jnp.asarray(ranks[vs]), jnp.asarray(svals[vs]),
                                bq=32))
    want = np.array([oracle[u, v] for u, v in zip(us, vs)])
    np.testing.assert_array_equal(got, want)


def test_empty_labels_queries():
    # a vertex in no hyperedge must answer 0 against everyone
    from repro.core import from_edge_lists, build_fast, mr_query
    h = from_edge_lists([[0, 1], [1, 2]], n=5)     # vertices 3, 4 isolated
    idx = build_fast(h)
    assert mr_query(idx, 3, 0) == 0
    assert mr_query(idx, 3, 4) == 0
    pidx = PaddedIndex(idx)
    assert int(pidx.mr(np.array([3]), np.array([0]))[0]) == 0


@jax.jit
def _searchsorted_mr(ranks, svals, us, vs):
    """The join ``batched_mr`` used before its sort-merge form: a binary
    search of each of u's keys in v's sorted row, kept as a second
    reference."""
    ru, su, rv, sv = ranks[us], svals[us], ranks[vs], svals[vs]
    pos = jax.vmap(jnp.searchsorted)(rv, ru)
    pos = jnp.minimum(pos, rv.shape[1] - 1)
    hit = jnp.take_along_axis(rv, pos, axis=1) == ru
    sv_at = jnp.take_along_axis(sv, pos, axis=1)
    return jnp.where(hit, jnp.minimum(su, sv_at), 0).max(axis=1)


def _label_rows(case, rng):
    """Ragged (ascending keys, s) label rows for one join case."""
    n = 24
    if case.startswith("closure"):   # every hyperedge a hub, every row full
        m = 45
        return ([np.arange(m, dtype=np.int32) for _ in range(n)],
                [rng.integers(0, 6, m).astype(np.int32) for _ in range(n)])
    width, key_lo, key_hi = {"lmax1": (1, 0, 4),
                             "width37": (37, 0, 120),
                             "width588": (588, 0, 1500),
                             "all_padding_rows": (20, 0, 60),
                             "u_equals_v": (30, 0, 90),
                             "max_rank_keys": (30, MAX_RANK - 80,
                                               MAX_RANK + 1)}[case]
    lengths = rng.integers(0, width + 1, n)
    lengths[:2] = width                             # the width is reached
    if case == "all_padding_rows":
        lengths[2::2] = 0
    ranks = [np.sort(rng.choice(np.arange(key_lo, key_hi), size=c,
                                replace=False)).astype(np.int32)
             for c in lengths]
    if case == "max_rank_keys":                     # the extremes occur
        ranks[0][-1] = ranks[1][-1] = MAX_RANK
        ranks[0][0] = ranks[1][0] = 0
    svals = [rng.integers(1, 9, c).astype(np.int32) for c in lengths]
    return ranks, svals


_GROWN = 64                                         # closure_patched width

# the closure rows re-landed on a 2 x 2 mesh: 45 columns pad to 46
_MESH_JOIN = """
import json
import jax, numpy as np
from jax.sharding import Mesh
from repro.core.query import DeviceSnapshot, batched_mr
ranks, svals, lengths, us, vs = (np.array(a, np.int32) for a in json.loads(%r))
mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
snap = DeviceSnapshot.from_padded(ranks, svals, lengths, "test").to_mesh(mesh)
assert snap.ranks.shape == (24, 46), snap.ranks.shape
print(json.dumps({"form": snap.join_form,
                  "picked": np.asarray(snap.mr(us, vs)).tolist(),
                  "merge": np.asarray(batched_mr(snap.ranks, snap.svals,
                                                 us, vs)).tolist()}))
"""


def _snapshot(case, rng):
    """The case's ``DeviceSnapshot`` and its ragged (keys, s) rows."""
    row_ranks, row_svals = _label_rows(case, rng)
    ranks, svals, lengths = pad_label_rows(row_ranks, row_svals)
    snap = DeviceSnapshot.from_padded(ranks, svals, lengths, "test")
    if case == "closure_patched":
        # the sharded closure's scoped refresh: dirty rows re-derived at a
        # grown width, untouched rows padded with INT32_MAX keys and s = 0
        dirty = np.arange(1, len(row_ranks), 3)
        keys = np.broadcast_to(np.arange(_GROWN, dtype=np.int32),
                               (dirty.size, _GROWN))
        s = rng.integers(0, 6, (dirty.size, _GROWN)).astype(np.int32)
        snap = snap.patch_rows(dirty, keys, s,
                               np.full(dirty.size, _GROWN, np.int32),
                               lmax=_GROWN)
        for i, u in enumerate(dirty):
            row_ranks[u], row_svals[u] = keys[i].copy(), s[i]
    return snap, row_ranks, row_svals


@pytest.mark.parametrize("bucket", [8, 64])
@pytest.mark.parametrize("case", ["lmax1", "width37", "width588",
                                  "closure_dense", "all_padding_rows",
                                  "u_equals_v", "max_rank_keys",
                                  "closure_patched", "closure_mesh"])
def test_batched_mr_exact(case, bucket):
    # both join forms against Algorithm 5 and the searchsorted join; the
    # snapshot takes the aligned form exactly on the closures' rows
    rng = np.random.default_rng([bucket, len(case)])
    snap, row_ranks, row_svals = _snapshot(case, rng)
    idx = types.SimpleNamespace(labels_rank=row_ranks, labels_s=row_svals)
    n = len(row_ranks)
    us = rng.integers(0, n, bucket).astype(np.int32)
    vs = rng.integers(0, n, bucket).astype(np.int32)
    vs[:4] = us[:4]
    if case == "u_equals_v":
        vs = us.copy()
    if case == "all_padding_rows":
        us[4:8], vs[4:8] = 2, np.array([4, 0, 1, 6])   # empty vs empty/full
    want = np.array([mr_query(idx, u, v) for u, v in zip(us, vs)])
    ranks, svals = np.asarray(snap.ranks), np.asarray(snap.svals)
    np.testing.assert_array_equal(
        want, np.asarray(_searchsorted_mr(ranks, svals, us, vs)))
    if case == "closure_mesh":
        out = json.loads(run_with_devices(_MESH_JOIN % json.dumps(
            [a.tolist() for a in (ranks, svals, np.asarray(snap.lengths),
                                  us, vs)])).strip().splitlines()[-1])
        assert out["form"] == "aligned"
        np.testing.assert_array_equal(out["picked"], want)
        np.testing.assert_array_equal(out["merge"], want)
        return
    aligned = case.startswith("closure")
    assert snap.join_form == ("aligned" if aligned else "merge")
    if case == "closure_patched":
        assert ranks.shape == (n, _GROWN)
        assert (ranks[0, 45:] == np.iinfo(np.int32).max).all()
    np.testing.assert_array_equal(np.asarray(snap.mr(us, vs)), want)
    np.testing.assert_array_equal(
        np.asarray(batched_mr(jnp.asarray(ranks), jnp.asarray(svals),
                              jnp.asarray(us), jnp.asarray(vs))), want)
    if aligned:
        np.testing.assert_array_equal(
            np.asarray(batched_mr_aligned(jnp.asarray(svals), jnp.asarray(us),
                                          jnp.asarray(vs))), want)
