#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,... [--control-seeds 7,8,9]

For every seed of ``--seeds``: one job of the cell, exactly as the window
runs it, compared with the float64 reference (`bench/check.py`).  For
every seed of ``--control-seeds``: the control in the program's place --
the reference computed in the precision just below the configuration's
(`reference.py`: three bf16 passes per product where the configuration
asks for float32 at HIGHEST, a float32 eigendecomposition) -- compared in
the same way.  One JSON line per reading, in one process, so that the
programs compile once.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_answers(x, y, x_test, cfg: dict, gamma: float,
                    landmark_seed: int, sample):
    """What the control answers in the job's place (`check.Answers`)."""
    import jax.numpy as jnp

    from bench import check, reference as ref
    feats, rank = ref.high_factor(x, cfg["budget"], gamma, landmark_seed)
    G = feats(x)
    tasks = ref.ovo_tasks(np.asarray(y), cfg["classes"])
    alphas, W = ref.high_solve(G, tasks, float(cfg["C"]), float(cfg["tol"]),
                               int(cfg["max_epochs"]))
    dec = np.asarray(ref.dot_high(jnp.asarray(feats(x_test)),
                                  jnp.asarray(W).T))
    dense = np.zeros((len(tasks), x.shape[0]), np.float32)
    for t, (rows, _) in enumerate(tasks):
        dense[t, rows] = alphas[t]
    return check.Answers(rank=rank, g_sample=G[sample], alpha=dense,
                         decisions=dec, labels=ref.vote(dec, cfg["classes"]))


def readings(workload: str, seed: int, control: bool,
             config_overrides: dict = None, traffic_overrides: dict = None):
    from bench import check, data, job, run
    _, _, cfg, traffic, _ = run.cell(workload, config_overrides,
                                     traffic_overrides)
    x, y, x_test, _, gamma = data.make_job(cfg, seed)
    lseed = data.landmark_seed(cfg)
    sample = check.sample_rows(x.shape[0], seed)
    t0 = time.perf_counter()
    info = {}
    if control:
        ans = control_answers(x, y, x_test, cfg, gamma, lseed, sample)
    else:
        rec, svm, dec, labels = job.run_job(
            cfg, traffic, gamma, lseed, x, y, x_test,
            lambda name: contextlib.nullcontext())
        ans = job.answers(svm, dec, labels, sample)
        info = {"job_s": rec.seconds, "epochs": max(rec.epochs),
                "rank": rec.rank, "smo_calls": rec.kernel_calls}
        del svm
    t1 = time.perf_counter()
    R = check.build_reference(x, y, x_test, cfg, gamma, lseed, seed)
    nums = check.compare(ans, R)
    return dict(nums, seed=seed, control=control, answer_s=t1 - t0,
                reference_s=time.perf_counter() - t1, ref_rank=R.factor.rank,
                **info)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import run
    run.add_libtpu_flags()
    _, wl, _, _, _ = run.cell(args.workload)
    devices, err = run.tpu_devices(wl["chips"])
    if err:
        print(f"control: {err}", file=sys.stderr)
        return 1
    run.enable_compile_cache()
    import jax
    seeds = lambda s: [int(v) for v in s.split(",") if v]
    with jax.default_device(devices[0]):
        for control, group in ((False, seeds(args.seeds)),
                               (True, seeds(args.control_seeds))):
            for seed in group:
                print(json.dumps(readings(args.workload, seed, control)),
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
