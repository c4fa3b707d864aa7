import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# ^ MUST precede every other import (jax locks device count on first init).
"""Paper-core dry-run: the reachability closure at production scale on the
production mesh — the workload that IS the paper's technique.

Cells (one squaring round each; a full closure is ⌈log2 m⌉ rounds):
  * maxmin-closure     m=65536, f32, 2-D block over (data, model) —
    (max,min) semiring, VPU-bound on TPU.
  * threshold-closure  m=65536 × S=32 thresholds, f32 boolean matmul —
    the MXU reformulation; S shards over `pod` on the multi-pod mesh.
  * bisection ladder   log2(S)=5 effective thresholds — the beyond-paper
    optimization (see EXPERIMENTS.md §Perf).

  PYTHONPATH=src python -m repro.launch.closure_dryrun --out results/dryrun_core
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import shard_map

from repro.launch.mesh import make_production_mesh
from repro.launch.hlo_analysis import loop_aware_collectives
from repro.core.distributed import sharded_maxmin_round

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9


VPU_OPS = 2.0e12        # ~f32 vector ops/s/chip (the maxmin form can't
                        # use the MXU — see DESIGN.md §2)


def lower_closure_cell(kind: str, m: int = 65536, s_thresholds: int = 32,
                       *, multi_pod: bool = False, schedule: str = "allgather",
                       dtype: str = "float32") -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(list(mesh.shape.values())))
    rec = dict(kind=kind, m=m, S=s_thresholds, multi_pod=multi_pod,
               schedule=schedule, dtype=dtype, n_devices=n_dev)
    axes = ("data", "model")
    dt = jnp.dtype(dtype)
    dbytes = dt.itemsize
    t0 = time.time()
    with mesh:
        if kind == "maxmin":
            spec = P(*axes)
            fn = jax.jit(sharded_maxmin_round(mesh, schedule=schedule,
                                              axes=axes))
            arg = jax.ShapeDtypeStruct((m, m), dt,
                                       sharding=NamedSharding(mesh, spec))
            lowered = fn.lower(arg)
            # one round of maxmin: 2·m³ compare/select ops — VPU rate
            flops = 2.0 * m ** 3
            hbm = 3 * m * m * dbytes
            peak = VPU_OPS
        else:
            s_eff = (int(np.ceil(np.log2(s_thresholds))) + 1
                     if kind == "bisection" else s_thresholds)
            batch_spec = (P("pod", *axes) if multi_pod else P(None, *axes))

            def round_body(blk):
                row = jax.lax.all_gather(blk, axes[1], axis=2, tiled=True)
                col = jax.lax.all_gather(blk, axes[0], axis=1, tiled=True)
                prod = jnp.einsum("sij,sjk->sik", row, col,
                                  preferred_element_type=jnp.float32)
                return (prod > 0).astype(blk.dtype)

            fn = jax.jit(shard_map(round_body, mesh=mesh,
                                       in_specs=batch_spec,
                                       out_specs=batch_spec))
            arg = jax.ShapeDtypeStruct((s_eff, m, m), dt,
                                       sharding=NamedSharding(mesh, batch_spec))
            lowered = fn.lower(arg)
            flops = 2.0 * s_eff * m ** 3          # MXU MACs
            hbm = 3 * s_eff * m * m * dbytes
            peak = PEAK_FLOPS if dtype == "bfloat16" else PEAK_FLOPS / 2
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    hlo = compiled.as_text()
    coll = loop_aware_collectives(hlo)
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
    except Exception:
        cost = {}
    t_comp = flops / (n_dev * peak)
    t_mem = hbm / (n_dev * HBM_BW)
    t_coll = coll["total_bytes"] / LINK_BW
    terms = dict(compute=t_comp, memory=t_mem, collective=t_coll)
    rec.update(status="ok", lower_s=round(t_lower, 2),
               compile_s=round(t_compile, 2),
               flops_analytic=flops, hbm_bytes=hbm,
               hlo_flops_per_dev=float(cost.get("flops", 0.0)),
               collective_executed={k: coll[k] for k in
                                    ("bytes", "counts", "total_bytes")},
               t_compute_s=t_comp, t_memory_s=t_mem, t_collective_s=t_coll,
               dominant=max(terms, key=terms.get),
               mfu_bound=(t_comp / max(terms.values())) if kind != "maxmin"
               else 0.0)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="results/dryrun_core")
    ap.add_argument("--m", type=int, default=65536)
    ap.add_argument("--S", type=int, default=32)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    cells = [("maxmin", False, "allgather", "float32"),
             ("maxmin", False, "ring", "float32"),
             ("threshold", False, "allgather", "float32"),
             ("threshold", True, "allgather", "float32"),
             ("threshold", False, "allgather", "bfloat16"),
             ("bisection", False, "allgather", "float32"),
             ("bisection", False, "allgather", "bfloat16"),
             ("bisection", True, "allgather", "bfloat16")]
    for kind, mp, sched, dtype in cells:
        tag = f"{kind}__{'mp' if mp else 'sp'}__{sched}__{dtype}"
        try:
            rec = lower_closure_cell(kind, args.m, args.S, multi_pod=mp,
                                     schedule=sched, dtype=dtype)
        except Exception as e:
            rec = dict(kind=kind, multi_pod=mp, schedule=sched, dtype=dtype,
                       status="error", error=f"{type(e).__name__}: {e}",
                       tb=traceback.format_exc()[-1500:])
        with open(os.path.join(args.out, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
        if rec["status"] == "ok":
            print(f"[ok   ] {tag:34s} comp={rec['t_compute_s']:.4f}s "
                  f"mem={rec['t_memory_s']:.4f}s "
                  f"coll={rec['t_collective_s']:.4f}s "
                  f"dominant={rec['dominant']} compile={rec['compile_s']}s")
        else:
            print(f"[error] {tag}: {rec['error']}")


if __name__ == "__main__":
    main()
