"""The rows build's per-round cost counts what one (max, min) round
touches, and its roofline share is a share."""
import itertools
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import build_cost  # noqa: E402


def _counted_round(r, w):
    """One round of R ← max(R, R ⊗ W) in loops, counting each min, max
    and compare, and each int32 read and written once."""
    n, m = r.shape
    out = np.zeros_like(r)
    ops = 0
    for v, e in itertools.product(range(n), range(m)):
        best = 0
        for k in range(m):
            best = max(best, min(r[v, k], w[k, e]))
            ops += 2
        out[v, e] = max(r[v, e], best)
        ops += 2                      # the fold and the fixpoint compare
    return out, ops, 4 * (w.size + r.size + out.size)


@pytest.mark.parametrize("n, m", [(1, 1), (3, 5), (4, 7)])
def test_rows_round_counts_one_round(n, m):
    rng = np.random.default_rng(n * 10 + m)
    r = rng.integers(0, 5, (n, m)).astype(np.int32)
    w = rng.integers(0, 5, (m, m)).astype(np.int32)
    out, ops, nbytes = _counted_round(r, w)
    want = np.maximum(r, np.minimum(r[:, :, None], w[None]).max(axis=1))
    assert np.array_equal(out, want)
    assert build_cost.rows_round(n, m) == {"ops": ops, "bytes": nbytes}


def test_email_eu_round():
    # 998 x 25,800: W is 2.66 GB of a round's 2.87 GB
    cost = build_cost.rows_round(998, 25_800)
    assert cost["bytes"] == 4 * (25_800 ** 2 + 2 * 998 * 25_800)
    assert cost["ops"] == 2 * 998 * 25_800 ** 2 + 2 * 998 * 25_800


def test_roofline_share():
    kind = "TPU v5 lite"
    per = build_cost.rows_round(998, 25_800)["bytes"]
    # 11 rounds at exactly the peak's pace read 100%
    at_peak = 11 * per / 819e9
    assert build_cost.roofline_share(998, 25_800, 11, at_peak, kind) == \
        pytest.approx(100.0)
    assert build_cost.roofline_share(998, 25_800, 11, 10 * at_peak,
                                     kind) == pytest.approx(10.0)
    with pytest.raises(KeyError):
        build_cost.roofline_share(1, 1, 1, 1.0, "no such chip")
