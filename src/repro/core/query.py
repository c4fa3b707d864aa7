"""Query processing (Section VI): Algorithm 5 + the JAX batched engine.

``mr_query`` is the faithful merge-join (labels sorted ascending by
importance rank; advance the pointer holding the more-important hub; skip
entries whose s cannot improve the running answer).

``batched_mr`` is the TPU-native serving path: labels exported as padded
dense tensors (``HLIndex.as_padded``), queries answered by a sort-merge
join — each query's two label rows are gathered whole and merged by one
sort of their concatenation, with no per-element gather, no loop and no
host pointer chasing, and a [Q]-sized batch is one XLA program.  This is the engine the paper's Exp-1 (1,000-query workload)
maps onto; it serves millions of queries per batch.  Where every row
holds hub j in column j (the closures' dense rows), ``batched_mr_aligned``
answers the same join with an elementwise min and a row max instead;
``DeviceSnapshot.join_form`` picks between the two from the snapshot's
data.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .hlindex import HLIndex

__all__ = ["mr_query", "s_reach_query", "mr_query_dicts", "DeviceSnapshot",
           "KernelSnapshot", "PaddedIndex", "batched_mr",
           "batched_mr_aligned"]


def mr_query(idx: HLIndex, u: int, v: int) -> int:
    """Algorithm 5: MR(u, v) from two sorted label lists."""
    ru, su = idx.labels_rank[u], idx.labels_s[u]
    rv, sv = idx.labels_rank[v], idx.labels_s[v]
    i = j = 0
    k = 0
    while i < ru.size and j < rv.size:
        if su[i] <= k or ru[i] < rv[j]:      # line 5
            i += 1
        elif sv[j] <= k or ru[i] > rv[j]:    # line 6
            j += 1
        else:                                # line 7: common hub, both s > k
            k = int(min(su[i], sv[j]))
            i += 1
            j += 1
    return k


def s_reach_query(idx: HLIndex, u: int, v: int, s: int) -> bool:
    """Problem 1 via the Section-VI modification: seed k = s-1; true on the
    first common-hub hit (early exit)."""
    ru, su = idx.labels_rank[u], idx.labels_s[u]
    rv, sv = idx.labels_rank[v], idx.labels_s[v]
    i = j = 0
    k = s - 1
    while i < ru.size and j < rv.size:
        if su[i] <= k or ru[i] < rv[j]:
            i += 1
        elif sv[j] <= k or ru[i] > rv[j]:
            j += 1
        else:
            return True
    return False


def mr_query_dicts(lu: Dict[int, int], lv: Dict[int, int],
                   rank: np.ndarray) -> int:
    """MR from dict-form labels (used by the minimization passes)."""
    if len(lu) > len(lv):
        lu, lv = lv, lu
    best = 0
    for e, s in lu.items():
        s2 = lv.get(e)
        if s2 is not None:
            m = min(s, s2)
            if m > best:
                best = m
    return best


# ---------------------------------------------------------------------------
# JAX batched engine
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _mesh_row_scatter(spec2d, spec1d, donate: bool):
    """Jitted dirty-row scatter for ``to_mesh(base=, dirty_rows=)``,
    cached per (sharding pair, donation) so periodic same-shaped
    snapshot refreshes reuse one compiled program instead of re-tracing
    every time (``NamedSharding`` is hashable, so the shardings are the
    cache key; shapes key jax's own jit cache underneath).  With
    ``donate`` the base tensors are donated to XLA, so the patch updates
    in place instead of allocating a second full label-mass copy."""
    @functools.partial(jax.jit, out_shardings=(spec2d, spec2d, spec1d),
                       donate_argnums=(0, 1, 2) if donate else ())
    def scatter(ranks, svals, lengths, idx, new_r, new_s, new_l):
        return (ranks.at[idx].set(new_r),
                svals.at[idx].set(new_s),
                lengths.at[idx].set(new_l))
    return scatter


@dataclasses.dataclass(eq=False)    # identity equality/hash: fields are arrays
class DeviceSnapshot:
    """Padded per-vertex label tensors on device, served by ``batched_mr``
    or, for key-aligned rows, ``batched_mr_aligned`` (``join_form``).

    Tensor layout and sentinel conventions:

    * ``ranks`` [n, Lmax] int32 — per-row **ascending** hub keys; rows
      shorter than Lmax are padded with ``INT32_MAX`` (2^31 - 1).  The
      padding sentinel can never equal a real hub key, so a padding slot
      only ever "matches" another padding slot — and then contributes
      ``min(0, 0) = 0`` to the join max, i.e. nothing.
    * ``svals`` [n, Lmax] int32 — the s-value carried by each label;
      padding slots hold 0 (0 = "no s-walk", the identity of the max).
    * ``lengths`` [n] int32 — true label counts per row (metadata for
      size accounting; the join itself relies only on the sentinels).

    The row key space only needs to be consistent across rows (hub
    importance rank for the HL-index/ETE backends, raw hyperedge id for
    the dense/sharded closures) — this is the one device-resident serving
    form every label-shaped backend of ``repro.core.engine`` exports.

    ``to_mesh`` re-lands the same tensors sharded over a device mesh via
    ``NamedSharding``, so one snapshot can outlive (and serve) any number
    of query batches on a multi-device topology.

    ``version`` records the engine version the snapshot was derived from
    (see ``ReachabilityEngine.update``): after an update, the engine's
    ``snapshot()`` re-derives a fresh snapshot with the bumped version,
    while previously handed-out snapshots keep their old version — a
    snapshot with ``snap.version != engine.version`` is stale.
    ``to_mesh`` propagates the version, so resharded copies stay
    comparable.

    Snapshots are immutable; incremental refresh produces *new* snapshots
    that reuse the old tensors: ``patch_rows`` replaces only the label
    rows a scoped update touched (the ``UpdateReport.refreshed_vertices``
    contract from ``repro.core.maintenance``), and ``to_mesh(base=...,
    dirty_rows=...)`` re-lands only those rows into an already
    mesh-resident copy instead of re-transferring the whole label mass.
    """

    ranks: jnp.ndarray
    svals: jnp.ndarray
    lengths: jnp.ndarray
    backend: str = "hl-index"
    version: int = 0

    @classmethod
    def from_padded(cls, ranks, svals, lengths, backend: str,
                    version: int = 0) -> "DeviceSnapshot":
        return cls(ranks=jnp.asarray(ranks), svals=jnp.asarray(svals),
                   lengths=jnp.asarray(lengths), backend=backend,
                   version=version)

    @classmethod
    def from_hlindex(cls, idx: HLIndex, backend: str = "hl-index",
                     version: int = 0) -> "DeviceSnapshot":
        ranks, svals, lengths = idx.as_padded()
        return cls.from_padded(ranks, svals, lengths, backend, version)

    def to_mesh(self, mesh, axes: Optional[Tuple[str, str]] = None, *,
                base: Optional["DeviceSnapshot"] = None,
                dirty_rows=None,
                donate_base: bool = False) -> "DeviceSnapshot":
        """Return this snapshot sharded over ``mesh`` via ``NamedSharding``:
        vertex rows split along ``axes[0]``, label columns along
        ``axes[1]`` (``lengths`` along ``axes[0]`` only).  ``axes=None``
        uses the mesh's last two axis names, so any axis naming works.

        Rows/columns are padded up to mesh-divisible sizes with the usual
        sentinels (ranks ``INT32_MAX``, svals 0), which are inert under
        the join — so the sharded snapshot answers identically.  The
        returned snapshot is committed to the mesh's devices and persists
        there across query batches; ``batched_mr`` consumes it directly
        (GSPMD partitions the gather + join).

        ``base`` + ``dirty_rows`` is the incremental re-land path used by
        the serving layer after a scoped update: when ``base`` is a
        previously ``to_mesh``-ed copy whose padded geometry matches this
        snapshot's, only the ``dirty_rows`` label rows are transferred and
        scattered into the resident tensors (everything else of ``base``
        is byte-identical by the ``UpdateReport`` contract).  On a
        geometry change (label width or vertex count re-padded
        differently) it falls back to a full re-land — answers are
        identical either way, only the transfer volume differs.
        ``donate_base`` additionally donates ``base``'s buffers to the
        scatter so the patch is in place (no transient second copy of
        the label mass) — ``base`` must not be used afterwards.  Ignored
        on CPU devices, where XLA cannot donate.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P
        if axes is None:
            axes = tuple(mesh.axis_names[-2:])
        if len(axes) < 2:
            raise ValueError(
                f"to_mesh needs two mesh axes (rows, label columns); the "
                f"mesh has axis names {mesh.axis_names}")
        row_ax, col_ax = axes
        r, c = mesh.shape[row_ax], mesh.shape[col_ax]
        n, lmax = self.ranks.shape
        n_pad = -(-n // r) * r if n else 0
        l_pad = -(-lmax // c) * c if lmax else 0
        spec2d = NamedSharding(mesh, P(row_ax, col_ax))
        spec1d = NamedSharding(mesh, P(row_ax))
        if (base is not None and dirty_rows is not None
                and tuple(base.ranks.shape) == (n_pad, l_pad)):
            rows = np.asarray(dirty_rows, np.int64)
            pr = np.full((rows.size, l_pad), np.iinfo(np.int32).max,
                         np.int32)
            ps = np.zeros((rows.size, l_pad), np.int32)
            pl = np.zeros(rows.size, np.int32)
            pr[:, :lmax] = np.asarray(self.ranks)[rows]
            ps[:, :lmax] = np.asarray(self.svals)[rows]
            pl[:] = np.asarray(self.lengths)[rows]
            donate = donate_base and all(
                d.platform != "cpu" for d in mesh.devices.flat)
            ranks, svals, lengths = _mesh_row_scatter(spec2d, spec1d,
                                                      donate)(
                base.ranks, base.svals, base.lengths,
                jnp.asarray(rows, jnp.int32), pr, ps, pl)
            return DeviceSnapshot(ranks=ranks, svals=svals, lengths=lengths,
                                  backend=self.backend, version=self.version)
        ranks = np.full((n_pad, l_pad), np.iinfo(np.int32).max, np.int32)
        svals = np.zeros((n_pad, l_pad), np.int32)
        lengths = np.zeros(n_pad, np.int32)
        ranks[:n, :lmax] = np.asarray(self.ranks)
        svals[:n, :lmax] = np.asarray(self.svals)
        lengths[:n] = np.asarray(self.lengths)
        return DeviceSnapshot(
            ranks=jax.device_put(ranks, spec2d),
            svals=jax.device_put(svals, spec2d),
            lengths=jax.device_put(lengths, spec1d),
            backend=self.backend, version=self.version)

    def patch_rows(self, rows, row_ranks, row_svals, row_lengths, *,
                   n: Optional[int] = None, lmax: Optional[int] = None,
                   version: Optional[int] = None,
                   backend: Optional[str] = None) -> "DeviceSnapshot":
        """A new snapshot with only ``rows`` replaced — the label-row
        re-derivation primitive behind snapshot caching across updates.

        ``row_ranks`` / ``row_svals`` are [len(rows), lmax] padded rows
        (``pad_label_rows(..., pad_to=lmax)`` form), ``row_lengths`` the
        true counts.  ``n`` / ``lmax`` resize the tensors first (rows
        appended with empty sentinel rows, columns padded with sentinels
        or sliced off) — legal because a clean row's content never
        exceeds the new ``lmax`` by the dirty-rows contract, so resizing
        touches only inert padding.  The result is byte-identical to a
        from-scratch derivation in which only ``rows`` changed; every
        untouched row is reused from this snapshot's device tensors
        without re-transfer.
        """
        ranks, svals, lengths = self.ranks, self.svals, self.lengths
        cur_n, cur_l = ranks.shape
        n = cur_n if n is None else int(n)
        lmax = cur_l if lmax is None else int(lmax)
        sentinel = np.iinfo(np.int32).max
        if lmax > cur_l:
            ranks = jnp.pad(ranks, ((0, 0), (0, lmax - cur_l)),
                            constant_values=sentinel)
            svals = jnp.pad(svals, ((0, 0), (0, lmax - cur_l)))
        elif lmax < cur_l:
            ranks = ranks[:, :lmax]
            svals = svals[:, :lmax]
        if n > cur_n:
            ranks = jnp.pad(ranks, ((0, n - cur_n), (0, 0)),
                            constant_values=sentinel)
            svals = jnp.pad(svals, ((0, n - cur_n), (0, 0)))
            lengths = jnp.pad(lengths, (0, n - cur_n))
        rows = jnp.asarray(np.asarray(rows, np.int64), jnp.int32)
        if rows.size:
            ranks = ranks.at[rows].set(jnp.asarray(row_ranks, jnp.int32))
            svals = svals.at[rows].set(jnp.asarray(row_svals, jnp.int32))
            lengths = lengths.at[rows].set(
                jnp.asarray(row_lengths, jnp.int32))
        return DeviceSnapshot(
            ranks=ranks, svals=svals, lengths=lengths,
            backend=self.backend if backend is None else backend,
            version=self.version if version is None else int(version))

    @property
    def lmax(self) -> int:
        return int(self.ranks.shape[1])

    def nbytes(self) -> int:
        return int(self.ranks.nbytes + self.svals.nbytes
                   + self.lengths.nbytes)

    @functools.cached_property
    def join_form(self) -> str:
        """Which join answers this snapshot, decided once from its data:
        ``"aligned"`` when every column j holds key j or carries s = 0
        (the closures' rows, padded or grown by any sentinel columns),
        so a common hub always sits in the same column of both rows and
        ``batched_mr_aligned`` needs no keys; ``"merge"`` otherwise
        (sparse HL-index / ETE labels), answered by ``batched_mr``.
        Computed on the first join (one device reduction, one scalar
        fetch) and kept for the snapshot's life."""
        return "aligned" if bool(_key_aligned(self.ranks, self.svals)) \
            else "merge"

    def mr(self, us, vs) -> jnp.ndarray:
        us = jnp.asarray(us)
        if self.lmax == 0:          # no labels anywhere: nothing is reachable
            return jnp.zeros(us.shape, jnp.int32)
        # key-aligned rows need no keys: batched_mr takes the aligned form
        ranks = None if self.join_form == "aligned" else self.ranks
        return batched_mr(ranks, self.svals, us, jnp.asarray(vs))

    def s_reach(self, us, vs, s: int) -> jnp.ndarray:
        return self.mr(us, vs) >= s


@functools.partial(jax.jit, donate_argnums=())
def _gather_rows(ranks, svals, us, vs):
    return ranks[us], svals[us], ranks[vs], svals[vs]


class KernelSnapshot:
    """Kernel-path query view over a ``DeviceSnapshot``.

    Answers ``mr`` / ``s_reach`` batches through the Pallas
    ``label_join`` kernel instead of the host merge-join or the XLA
    ``batched_mr`` program: query rows are gathered from the resident
    label tensors on device, the batch is padded up to a power-of-two
    bucket (the same admission-bucket policy ``ReachabilityService``
    uses, so serving traffic compiles one kernel program per bucket
    shape, not per batch size), and the [bucket, Lmax] rows feed
    ``label_join_pallas``.  Memory stays label-mass: the view holds no
    tensors of its own beyond the wrapped snapshot.

    The wrapped ``base`` snapshot keeps its identity — patch/re-land
    plumbing (``patch_rows``, ``to_mesh(base=...)``) operates on the
    underlying ``DeviceSnapshot`` and the view is rebuilt around the
    result, which is why this is composition rather than subclassing.

    ``interpret=None`` resolves the Pallas execution mode from the
    default backend (``use_interpret()``): compiled on TPU, interpreted
    on the CPU backend the tests run on, and an error anywhere else.
    Construction validates the rank key space against the kernel's
    padding sentinels once (``validate_ranks``), so per-batch calls
    don't pay the check.
    """

    def __init__(self, base: DeviceSnapshot, *, bq: int = 128,
                 bl: int = 256, min_bucket: int = 8,
                 interpret: Optional[bool] = None):
        from ..kernels.label_join import label_join_pallas, validate_ranks
        from ..kernels.ops import use_interpret
        validate_ranks(base.ranks)
        self.base = base
        self._join = label_join_pallas
        self._bq = int(bq)
        self._bl = int(bl)
        self._min_bucket = max(1, int(min_bucket))
        self.interpret = use_interpret() if interpret is None else bool(
            interpret)

    # geometry / identity delegate to the wrapped snapshot
    @property
    def backend(self) -> str:
        return self.base.backend

    @property
    def version(self) -> int:
        return self.base.version

    @property
    def lmax(self) -> int:
        return self.base.lmax

    def nbytes(self) -> int:
        return self.base.nbytes()

    def _bucket(self, q: int) -> int:
        b = self._min_bucket
        while b < q:
            b *= 2
        return b

    def mr(self, us, vs) -> jnp.ndarray:
        us = np.asarray(us, np.int32).ravel()
        vs = np.asarray(vs, np.int32).ravel()
        q = us.size
        if q == 0 or self.base.lmax == 0:
            return jnp.zeros((q,), jnp.int32)
        bucket = self._bucket(q)
        if bucket > q:
            # pad with a repeat of the first pair: always in range, and
            # the padded answers are sliced off below
            us = np.concatenate([us, np.full(bucket - q, us[0], np.int32)])
            vs = np.concatenate([vs, np.full(bucket - q, vs[0], np.int32)])
        ru, su, rv, sv = _gather_rows(self.base.ranks, self.base.svals,
                                      jnp.asarray(us), jnp.asarray(vs))
        if len(self.base.ranks.devices()) > 1:
            # mesh-sharded base: the kernel runs on one device, so
            # collapse the gathered query rows (bucket × Lmax, not the
            # label mass) onto a single addressable device
            dev = next(iter(sorted(self.base.ranks.devices(),
                                   key=lambda d: d.id)))
            ru, su, rv, sv = (jax.device_put(t, dev)
                              for t in (ru, su, rv, sv))
        out = self._join(ru, su, rv, sv, bq=min(self._bq, bucket),
                         bl=self._bl, interpret=self.interpret)
        return out[:q]

    def s_reach(self, us, vs, s: int) -> jnp.ndarray:
        return self.mr(us, vs) >= s


class PaddedIndex(DeviceSnapshot):
    """Back-compat constructor: the padded device form built straight from
    an ``HLIndex``.  New code should use ``DeviceSnapshot.from_hlindex``
    (or ``engine.snapshot()`` through ``repro.api``)."""

    def __init__(self, idx: HLIndex):
        ranks, svals, lengths = idx.as_padded()
        super().__init__(ranks=jnp.asarray(ranks), svals=jnp.asarray(svals),
                         lengths=jnp.asarray(lengths), backend="hl-index")


@functools.partial(jax.jit, donate_argnums=())
def batched_mr(ranks: Optional[jax.Array], svals: jax.Array,
               us: jax.Array, vs: jax.Array) -> jax.Array:
    """MR(u, v) for a batch of query pairs.

    ``ranks=None`` says the rows are key-aligned (column j holds hub j or
    s = 0; ``DeviceSnapshot.join_form``) and answers by
    ``batched_mr_aligned``, which reads no keys.  Otherwise both label
    rows of each query are gathered whole (row gathers, which the chip
    does as DMAs) and concatenated; one sort of each [2 Lmax]
    row by hub key, carrying s along, lines a hub common to u and v up
    in two adjacent slots, since a key appears at most once per row.
    The answer is the largest min(s_u, s_v) over equal adjacent keys.
    Padding (INT32_MAX, s = 0) only ever meets padding and adds
    min(0, 0) = 0.  Equivalent to Algorithm 5's merge-join, with no
    per-element gather and no loop; the cost per query depends only on
    the row width.
    """
    if ranks is None:
        return batched_mr_aligned(svals, us, vs)
    keys = jnp.concatenate([ranks[us], ranks[vs]], axis=1)      # [Q, 2L]
    s = jnp.concatenate([svals[us], svals[vs]], axis=1)
    keys, s = jax.lax.sort((keys, s), dimension=1, num_keys=1)
    hit = keys[:, 1:] == keys[:, :-1]
    return jnp.where(hit, jnp.minimum(s[:, 1:], s[:, :-1]), 0).max(axis=1)


@jax.jit
def _key_aligned(ranks: jax.Array, svals: jax.Array) -> jax.Array:
    """True when every slot with s > 0 sits in the column its key names."""
    cols = jnp.arange(ranks.shape[1], dtype=ranks.dtype)
    return jnp.all((ranks == cols[None, :]) | (svals == 0))


@functools.partial(jax.jit, donate_argnums=())
def batched_mr_aligned(svals: jax.Array, us: jax.Array,
                       vs: jax.Array) -> jax.Array:
    """MR(u, v) for a batch of query pairs over key-aligned rows.

    Where column j holds hub j in every row (or s = 0), a hub common to
    u and v sits in the same column of both rows, so the join is
    max_j min(s_u[j], s_v[j]): two row gathers, an elementwise min and
    a row max, with no keys, no sort and no data-dependent work.  Exact,
    and equal to the sort-merge on such rows; ``DeviceSnapshot.join_form``
    decides when it applies, and ``batched_mr(None, ...)`` calls it.
    """
    return jnp.minimum(svals[us], svals[vs]).max(axis=1)
