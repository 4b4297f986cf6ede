import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Dry-run of the PAPER'S OWN workload on the production mesh.

Lowers + compiles the two LPD-SVM stages at server scale (the paper's
largest settings: n = 10^7, B = 10^4, p = 256 dense features):

  stage1-gram     K(x, landmarks): rows sharded ("pod","data"), landmark
                  axis sharded "model" — the cuBLAS batch-kernel step.
  stage1-project  G = K_nm @ projector, contraction over the "model"-sharded
                  budget axis (reduce-scatter visible in the schedule).
  stage2-farm     shard_map task farm: 512 OVO/CV binary problems solved
                  concurrently, one per device (the paper's multi-GPU grid
                  search, 11,250 SVMs at a time).

    PYTHONPATH=src python -m repro.launch.dryrun_svm [--multi-pod]
"""
import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo import collective_stats
from repro.core.distributed import stage1_gram_sharded, stage1_project_sharded
from repro.core.dual_solver import SolverConfig, TaskBatch, solve_batch
from repro.core.kernel_fn import KernelParams
from repro.launch.mesh import make_production_mesh

RESULTS_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "results", "dryrun"))


def run(multi_pod: bool, n: int, budget: int, p: int, task_rows: int,
        out_dir: str):
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rows = ("pod", "data") if multi_pod else ("data",)
    n_dev = int(np.prod(list(mesh.shape.values())))
    kp = KernelParams("rbf", gamma=2 ** -7)
    recs = {}

    def record(name, lowered):
        c = lowered.compile()
        ma = c.memory_analysis()
        recs[name] = {
            "temp_bytes": ma.temp_size_in_bytes,
            "argument_bytes": ma.argument_size_in_bytes,
            "cost": {k: v for k, v in c.cost_analysis().items()
                     if k in ("flops", "bytes accessed")},
            "collectives": collective_stats(c.as_text()),
        }
        print(f"[ok] svm-{name} ({mesh_name})  "
              f"temp={ma.temp_size_in_bytes/2**30:.2f}GiB "
              f"flops={recs[name]['cost'].get('flops', 0):.3e}", flush=True)

    with jax.set_mesh(mesh):
        x_sds = jax.ShapeDtypeStruct((n, p), jnp.float32,
                                     sharding=NamedSharding(mesh, P(rows, None)))
        lm_sds = jax.ShapeDtypeStruct((budget, p), jnp.float32,
                                      sharding=NamedSharding(mesh, P("model", None)))
        gram = stage1_gram_sharded(mesh, kp, row_axes=rows)
        record("stage1-gram", gram.lower(x_sds, lm_sds))

        knm_sds = jax.ShapeDtypeStruct((n, budget), jnp.float32,
                                       sharding=NamedSharding(mesh, P(rows, "model")))
        proj_sds = jax.ShapeDtypeStruct((budget, budget), jnp.float32,
                                        sharding=NamedSharding(mesh, P(None, None)))
        project = stage1_project_sharded(mesh, row_axes=rows)
        record("stage1-project", project.lower(knm_sds, proj_sds))

        from repro.core.distributed import stage1_project_sharded_v2
        project_v2 = stage1_project_sharded_v2(mesh, row_axes=rows)
        record("stage1-project-v2", project_v2.lower(knm_sds, proj_sds))

        # stage 2: one binary task per device over a replicated G
        T = n_dev
        n_pad = task_rows
        g_sds = jax.ShapeDtypeStruct((n_pad * 4, budget), jnp.float32,
                                     sharding=NamedSharding(mesh, P(None, None)))
        tspec = NamedSharding(mesh, P(tuple(mesh.axis_names)))
        tb = TaskBatch(
            idx=jax.ShapeDtypeStruct((T, n_pad), jnp.int32, sharding=tspec),
            y=jax.ShapeDtypeStruct((T, n_pad), jnp.float32, sharding=tspec),
            c=jax.ShapeDtypeStruct((T, n_pad), jnp.float32, sharding=tspec),
            alpha0=jax.ShapeDtypeStruct((T, n_pad), jnp.float32, sharding=tspec),
        )
        cfgs = SolverConfig(tol=1e-2, max_epochs=100)

        def farm(G, idx, y, c, a0):
            from repro.core.distributed import solve_tasks_sharded
            return solve_tasks_sharded(G, TaskBatch(idx, y, c, a0), cfgs, mesh)

        record("stage2-farm", jax.jit(farm).lower(g_sds, tb.idx, tb.y, tb.c,
                                                  tb.alpha0))

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"svm-workload__{mesh_name}.json"), "w") as f:
        json.dump({"mesh": mesh_name, "n": n, "budget": budget, "p": p,
                   "stages": recs}, f, indent=1)
    return recs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both", action="store_true")
    ap.add_argument("--n", type=int, default=10_002_432)  # divisible by 512 devices
    ap.add_argument("--budget", type=int, default=10_000)
    ap.add_argument("--p", type=int, default=256)
    ap.add_argument("--task-rows", type=int, default=65536)
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args()
    modes = [False, True] if args.both else [args.multi_pod]
    for mp in modes:
        run(mp, args.n, args.budget, args.p, args.task_rows, args.out)


if __name__ == "__main__":
    main()
