"""jit'd dispatch wrappers for the Pallas kernels.

On TPU the compiled kernels run natively; on the CPU backend (the test
suite) they run under ``interpret=True`` — same kernel body,
Python-evaluated, validated against ``ref.py``.  Any other backend has
no way to run them and raises.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .maxmin_matmul import maxmin_matmul_pallas
from .overlap import overlap_pallas
from .threshold_closure import threshold_step_pallas
from .label_join import label_join_pallas

__all__ = ["maxmin_matmul", "overlap", "threshold_step", "label_join",
           "maxmin_closure_kernel", "threshold_mr_kernel", "use_interpret",
           "interpret_available"]


def use_interpret() -> bool:
    """Compiled on TPU, interpreted on CPU; any other backend raises."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels run compiled on TPU or interpreted on CPU; "
        f"the default JAX backend is {backend!r}")


_INTERPRET_PROBE: Optional[bool] = None


def interpret_available() -> bool:
    """Whether ``pallas_call(interpret=True)`` works on this host.

    Probed once with a tiny kernel and cached; tests use it to skip
    cleanly on builds where the Pallas interpreter is unavailable
    (e.g. a jaxlib compiled without the Mosaic interpret path).
    """
    global _INTERPRET_PROBE
    if _INTERPRET_PROBE is None:
        try:
            from jax.experimental import pallas as pl

            def _copy(x_ref, o_ref):
                o_ref[...] = x_ref[...]

            x = jnp.arange(8, dtype=jnp.int32)
            out = pl.pallas_call(
                _copy, out_shape=jax.ShapeDtypeStruct((8,), jnp.int32),
                interpret=True)(x)
            _INTERPRET_PROBE = bool((np.asarray(out) == np.arange(8)).all())
        except Exception:
            _INTERPRET_PROBE = False
    return _INTERPRET_PROBE


def maxmin_matmul(a, b, *, bm: int = 128, bn: int = 128, bk: int = 128):
    return maxmin_matmul_pallas(a, b, bm=bm, bn=bn, bk=bk,
                                interpret=use_interpret())


def overlap(b_inc, *, bm: int = 128, bn: int = 128, bk: int = 128):
    return overlap_pallas(b_inc, bm=bm, bn=bn, bk=bk,
                          interpret=use_interpret())


def threshold_step(r, *, bm: int = 128, bn: int = 128, bk: int = 128):
    return threshold_step_pallas(r, bm=bm, bn=bn, bk=bk,
                                 interpret=use_interpret())


def label_join(ru, su, rv, sv, *, bq: int = 128):
    return label_join_pallas(ru, su, rv, sv, bq=bq,
                             interpret=use_interpret())


# ---------------------------------------------------------------------------
# closure drivers on top of the kernels
# ---------------------------------------------------------------------------

def maxmin_closure_kernel(w: jax.Array, *, rounds: Optional[int] = None,
                          **blocks) -> jax.Array:
    """Bottleneck closure via the Pallas (max,min) kernel."""
    m = w.shape[0]
    n_rounds = rounds if rounds is not None else max(1, int(np.ceil(np.log2(max(m, 2)))))
    r = w
    for _ in range(n_rounds):
        r = jnp.maximum(r, maxmin_matmul(r, r, **blocks))
    return r


def threshold_mr_kernel(w: jax.Array, thresholds: np.ndarray, *,
                        rounds: Optional[int] = None, **blocks) -> jax.Array:
    """MR matrix via the fused threshold-closure kernel (MXU path)."""
    m = w.shape[0]
    n_rounds = rounds if rounds is not None else max(1, int(np.ceil(np.log2(max(m, 2)))))
    t = jnp.asarray(thresholds)
    adj = (w[None, :, :] >= t[:, None, None]).astype(jnp.float32)
    eye = jnp.eye(m, dtype=jnp.float32)[None]
    r = jnp.maximum(adj, eye)
    for _ in range(n_rounds):
        r = threshold_step(r, **blocks)
    mr = (r * t[:, None, None].astype(jnp.float32)).max(axis=0)
    mr = mr.at[jnp.arange(m), jnp.arange(m)].set(jnp.diagonal(w).astype(jnp.float32))
    return mr.astype(w.dtype)
