"""Ahead-of-time compiles of the main-path programs for a TPU v5e.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached, so these tests run on a CPU-only host.  They
catch what the Pallas interpreter cannot: block shapes that break the
(8, 128) tiling rules, vector ops Mosaic cannot lay out, and kernels
that need more VMEM than a core has.  Shapes are the serving path's real
widths (the walmart-trips analog: n = 88,860 vertices).

Only one process may load libtpu at a time, so the topology is described
inside a module-scoped fixture — never at import time — and every
compile runs in the test's own process.  All such compiles live in this
one file so that a single xdist worker loads the library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.core.distributed import sharded_maxmin_round
from repro.core.query import batched_mr
from repro.kernels.label_join import label_join_pallas
from repro.kernels.maxmin_matmul import maxmin_matmul_pallas
from repro.kernels.threshold_closure import threshold_step_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                             # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one; keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("width", [16, 256, 1024])
def test_label_join_compiles(one_chip, width):
    rows = _spec((1024, width), jnp.int32, one_chip)
    compiled = label_join_pallas.lower(rows, rows, rows, rows,
                                       interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_maxmin_matmul_compiles(one_chip):
    a = _spec((1024, 1024), jnp.float32, one_chip)
    compiled = maxmin_matmul_pallas.lower(a, a, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_threshold_step_compiles(one_chip):
    r = _spec((4, 1024, 1024), jnp.float32, one_chip)
    compiled = threshold_step_pallas.lower(r, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_batched_mr_compiles(one_chip):
    labels = _spec((88_860, 16), jnp.int32, one_chip)
    queries = _spec((4096,), jnp.int32, one_chip)
    compiled = batched_mr.lower(labels, labels, queries, queries).compile()
    stats = compiled.memory_analysis()
    # the [Q, L] gathers and join temporaries, nowhere near the 16 GB HBM
    assert stats.temp_size_in_bytes < 64 * 2**20


@pytest.mark.parametrize("n, width, q, temp_bound", [
    (327, 7818, 4096, 3 * 2**30),      # closure rows, a full 4,096 bucket
    (998, 25_800, 4096, 4 * 2**30),    # email-eu's closure rows
    (11_107, 588, 64, 64 * 2**20),     # HL-index rows, an open-loop bucket
])
def test_batched_mr_compiles_loop_free(one_chip, n, width, q, temp_bound):
    # the join is one program with no loop: a per-element search loop
    # (one gather per slot per step) is what made the chip crawl
    labels = _spec((n, width), jnp.int32, one_chip)
    queries = _spec((q,), jnp.int32, one_chip)
    compiled = batched_mr.lower(labels, labels, queries, queries).compile()
    assert "while" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < temp_bound


def test_closure_rows_build_compiles(one_chip):
    # email-eu (998 x 25,800): the overlap matrix from the members on the
    # MXU, then the propagation loop, with no [n, block, m] temporary
    from repro.core.semiring import _line_graph, _rows_fixpoint
    n, m, k = 998, 25_800, 26_112
    line = _line_graph.lower(_spec((k, 25), jnp.int32, one_chip),
                             _spec((m,), jnp.int32, one_chip),
                             n=n).compile()
    assert line.memory_analysis().temp_size_in_bytes < 64 * 2**20
    rows = _rows_fixpoint.lower(_spec((n, m), jnp.int32, one_chip),
                                _spec((k, m), jnp.int32, one_chip),
                                block=512).compile()
    assert "while" in rows.as_text()
    assert rows.memory_analysis().temp_size_in_bytes < 512 * 2**20


@pytest.mark.parametrize("schedule", ["allgather", "ring"])
def test_sharded_round_with_kernels_compiles(topo, monkeypatch, schedule):
    # the closure round picks interpret mode from the default backend,
    # which is the CPU here; steer it to the compiled kernel the chip runs
    monkeypatch.setattr("repro.kernels.ops.use_interpret", lambda: False)
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    round_fn = sharded_maxmin_round(mesh, schedule=schedule,
                                    use_kernels=True)
    w = _spec((16_384, 16_384), jnp.float32,
              NamedSharding(mesh, P("data", "model")))
    compiled = jax.jit(round_fn).lower(w).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("all-gather" in text) or ("collective-permute" in text)
