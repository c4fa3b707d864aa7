"""Operations and bytes of the closure regime's rows build, per round.

``repro.core.semiring.vertex_rows`` builds the rows ``R`` [n, m] int32
from the overlap matrix ``W`` [m, m] int32 by rounds of

    R ← max(R, R ⊗ W),    (R ⊗ W)[v, e] = max_k min(R[v, k], W[k, e]),

until a round changes nothing.  A round needs, whichever way it is
tiled:

* operations: a min and a max for each of the n·m·m (v, k, e) triples,
  and a max and a compare for each of the n·m entries of the new rows
  (the fold into the old rows and the fixpoint test);
* bytes: W read once, the old rows read once and the new rows written
  once, all int32: ``4·(m·m + 2·n·m)``.

These are the work needed, not the work done: a tiling that reads W or
the rows more than once moves more, and the padding of the contraction
to a whole block is not counted.  ``roofline_share`` divides the least
time the HBM needs for them by the device time a trace gives for the
rounds; there is no published integer peak of the VPU, so no compute
term.  The rows program is ``_rows_fixpoint``.
"""
from __future__ import annotations

import tracefile

__all__ = ["rows_round", "roofline_share", "ROWS_PROGRAM"]

ROWS_PROGRAM = "_rows_fixpoint"


def rows_round(n: int, m: int) -> dict:
    """``{"ops": ..., "bytes": ...}`` one round of the rows build needs."""
    n, m = int(n), int(m)
    return {"ops": 2 * n * m * m + 2 * n * m,
            "bytes": 4 * (m * m + 2 * n * m)}


def roofline_share(n: int, m: int, rounds: int, device_s: float,
                   device_kind: str) -> float:
    """Percent of the HBM roofline the rows build reached: the bytes of
    ``rounds`` rounds over the peak bandwidth, over ``device_s``, the
    device time of the rows program."""
    hbm = tracefile.peak(device_kind)["hbm_bytes_per_s"]
    return 100.0 * rounds * rows_round(n, m)["bytes"] / hbm / device_s
