import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combination.

For each combination this driver produces:
  * the full scanned-layers lowering, compiled on the production mesh —
    memory_analysis() proves the per-device footprint fits, and the HLO is
    kept for the collective schedule;
  * two small UNROLLED "probe" lowerings (1 and 2 layer groups) whose
    cost_analysis() and collective bytes are exact (no scan bodies, single
    flash chunk), extrapolated linearly to the full depth:
        total = probe1 + (n_groups - 1) * (probe2 - probe1)
    (XLA's HloCostAnalysis counts while-loop bodies ONCE, so the full
    lowering's FLOP numbers would undercount scanned layers.)

Results land in results/dryrun/<arch>__<shape>__<mesh>.json, consumed by
`repro.analysis.roofline` and EXPERIMENTS.md.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b \
        --shape train_4k --mesh single           # one combo
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro.analysis.hlo import collective_stats
from repro.configs import ARCH_IDS, get_config
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_prefill_step, make_serve_step, make_train_step
from repro.models import attention as attn_mod
from repro.optim import get_optimizer

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def probe_config(cfg, n_groups: int):
    """Reduce depth to `first_dense_layers + n_groups * group` layers."""
    from repro.models.model import _group_size
    g = _group_size(cfg)
    changes = {"n_layers": cfg.first_dense_layers + n_groups * g}
    if cfg.is_encoder_decoder:
        changes["n_encoder_layers"] = n_groups
    return dataclasses.replace(cfg, **changes)


def lower_combo(cfg, shape, mesh, *, unroll: bool):
    """Lower the right step for `shape.mode`; returns (lowered, n_groups)."""
    from repro.models.model import _layout
    B = shape.global_batch
    with jax.set_mesh(mesh):
        params_sds, _ = S.param_specs(cfg, mesh)
        if shape.mode == "train":
            opt = get_optimizer(cfg.optimizer)
            opt_sds = S.opt_state_specs(opt, params_sds)
            step = make_train_step(cfg, opt, mesh, global_batch=B,
                                   unroll=unroll)
            lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
                params_sds, opt_sds, S.batch_specs(cfg, shape, mesh))
        elif shape.mode == "prefill":
            step = make_prefill_step(cfg, mesh, global_batch=B, unroll=unroll)
            lowered = jax.jit(step).lower(params_sds,
                                          S.batch_specs(cfg, shape, mesh))
        else:
            step = make_serve_step(cfg, mesh, global_batch=B, unroll=unroll)
            ins = S.serve_input_specs(cfg, shape, mesh)
            lowered = jax.jit(step, donate_argnums=(2,)).lower(
                params_sds, ins["tokens"], ins["state"], ins["pos"])
    return lowered, _layout(cfg)[2]


def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
            *, skip_probes: bool = False) -> dict:
    cfg = get_config(arch)
    shape = S.SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    tag = f"{arch}__{shape_name}__{mesh_name}"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "mode": shape.mode, "status": "ok"}
    t0 = time.time()
    try:
        # ---- full lowering: compile proof + memory + collective schedule
        lowered, n_groups = lower_combo(cfg, shape, mesh, unroll=False)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
        }
        rec["timings"] = {"lower_s": round(t_lower, 1),
                          "compile_s": round(t_compile, 1)}
        rec["full_cost"] = {k: v for k, v in compiled.cost_analysis().items()
                            if k in ("flops", "bytes accessed")}
        rec["full_collectives"] = collective_stats(compiled.as_text())
        rec["n_groups"] = n_groups

        if not skip_probes:
            # ---- probe extrapolation (exact per-group costs)
            attn_mod.FLASH_KV_CHUNK = 1 << 30
            try:
                probes = []
                for k in (1, 2):
                    pl, _ = lower_combo(probe_config(cfg, k), shape, mesh,
                                        unroll=True)
                    pc = pl.compile()
                    probes.append({
                        "cost": pc.cost_analysis(),
                        "coll": collective_stats(pc.as_text()),
                    })
            finally:
                attn_mod.FLASH_KV_CHUNK = 1024

            def extra(sel):
                # per-group delta clamped >= 0: probe fusion noise can make
                # p2 marginally smaller than p1 for near-zero terms
                p1, p2 = sel(probes[0]), sel(probes[1])
                return p1 + (n_groups - 1) * max(0.0, p2 - p1)

            rec["flops"] = extra(lambda p: p["cost"].get("flops", 0.0))
            rec["bytes_accessed"] = extra(
                lambda p: p["cost"].get("bytes accessed", 0.0))
            rec["collective_bytes"] = extra(
                lambda p: p["coll"]["weighted_bytes"])
            rec["collective_detail"] = {
                "probe1": probes[0]["coll"], "probe2": probes[1]["coll"]}
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec["status"] = "FAIL"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["wall_s"] = round(time.time() - t0, 1)

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[{rec['status']}] {tag}  wall={rec['wall_s']}s "
          f"temp={rec.get('memory', {}).get('temp_bytes', 0)/2**30:.2f}GiB "
          f"flops={rec.get('flops', 0):.3e}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-probes", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(RESULTS_DIR))
    args = ap.parse_args()

    archs = list(ARCH_IDS) if (args.all or args.arch is None) else [args.arch]
    shapes = list(S.SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    fails = 0
    for multi in meshes:
        for arch in archs:
            for shape in shapes:
                rec = run_one(arch, shape, multi, args.out,
                              skip_probes=args.skip_probes)
                fails += rec["status"] != "ok"
    print(f"done; {fails} failures")
    raise SystemExit(1 if fails else 0)


if __name__ == "__main__":
    main()
