"""Stage-2 task-farm scaling: serial per-device streams vs the overlapped
shared-reader farm (`core/distributed.py::solve_tasks_streamed`).

For each device count D the same (G, TaskBatch) pair is solved by
  * the legacy SERIAL farm (each device's block stream driven to completion
    in turn — G re-read once per device, wall-clock ~ sum of shards), and
  * the OVERLAPPED farm (one shared host reader stages each (tile, B) block
    once per pass and fans it out to per-device worker queues),
recording wall-clock and the mesh-level H2D bytes of the first full pass —
the number that must NOT scale with D for the overlapped farm (the paper's
"parallelism + more RAM" leg: many cores feeding multiple devices out of one
large-RAM host copy of G).  Every D runs in this one process, over
``jax.local_devices()[:D]``: a child process could not reach an accelerator
the parent already holds.  On the CPU the devices are virtual and come from
the environment (`make bench` sets ``XLA_FLAGS``):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m benchmarks.run stage2_mesh
    BENCH_SMOKE=1 ...                                     # fast
"""
from __future__ import annotations

import json
import os
import time

OUT_PATH = os.environ.get("BENCH_STAGE2_MESH_JSON", "BENCH_stage2_mesh.json")
SMOKE = bool(os.environ.get("BENCH_SMOKE"))

# Virtual host devices beyond the PHYSICAL core count measure thread
# oversubscription, not the farm (the real target is D actual accelerators
# fed by many host cores), so device counts are capped at cpu_count.
_CORES = os.cpu_count() or 1
DEVICE_COUNTS = tuple(d for d in ((1, 2) if SMOKE else (1, 2, 4))
                      if d <= max(_CORES, 1)) or (1,)
# (n, budget, classes, max_epochs); blocks are kept fat (TILE) so per-call
# XLA compute — which releases the GIL and genuinely parallelises across
# device worker threads — dominates the Python dispatch per block
PROBLEM = (2_400, 128, 4, 12) if SMOKE else (8_000, 192, 4, 25)
TILE = 1_024 if SMOKE else 2_048


def _records(G, tasks, devices) -> list:
    """Solve the same problem through both farms on ``devices``; one record
    per mode."""
    from benchmarks.common import timeit
    from repro.core import SolverConfig, StreamConfig, solve_tasks_streamed

    n, _, _, max_epochs = PROBLEM
    config = SolverConfig(tol=1e-2, max_epochs=max_epochs)
    scfg = StreamConfig(tile_rows=TILE)
    records = []
    for mode, overlap in (("serial", False), ("overlapped", True)):
        holder = {}

        def solve():
            holder["st"] = solve_tasks_streamed(
                G, tasks, config, devices=devices, stream_config=scfg,
                overlap=overlap, return_stats=True)[1]

        # warmup compiles this mode's jits; the median of 5 timed solves
        # tames the scheduler noise of a small container (smoke: 1 run)
        t = timeit(solve, repeats=1 if SMOKE else 5)
        st = holder["st"]
        records.append({
            "mode": mode, "n_devices": len(devices), "n": n,
            "rank": G.shape[1],
            "n_tasks": tasks.n_tasks, "tile_rows": st.tile_rows,
            "seconds": t, "bytes_h2d": st.bytes_h2d,
            "bytes_put": st.bytes_put,
            "first_pass_bytes": st.epoch_bytes[0] if st.epoch_bytes else None,
            "epochs": st.epochs, "full_passes": st.full_passes,
            "prefetch_final": st.prefetch_final,
        })
    return records


def run() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import emit, provenance
    from repro.core import KernelParams, compute_factor
    from repro.core.ovo import build_ovo_tasks
    from repro.data import make_multiclass

    n, budget, classes, _ = PROBLEM
    x, y = make_multiclass(n, p=8, n_classes=classes, seed=7)
    _, labels = np.unique(y, return_inverse=True)
    fac = compute_factor(jnp.asarray(x, jnp.float32),
                         KernelParams("rbf", gamma=0.2), budget)
    G = np.asarray(fac.G)
    tasks, _ = build_ovo_tasks(labels, classes, 4.0)
    devices = jax.local_devices()
    records = []
    for n_dev in (d for d in DEVICE_COUNTS if d <= len(devices)):
        recs = _records(G, tasks, devices[:n_dev])
        records.extend(recs)
        by_mode = {r["mode"]: r for r in recs}
        speedup = by_mode["serial"]["seconds"] / by_mode["overlapped"]["seconds"]
        for r in recs:
            emit(f"stage2_mesh_{r['mode']}_D{n_dev}", r["seconds"] * 1e6,
                 f"{r['first_pass_bytes'] / 2**20:.1f}MiB/pass h2d")
        emit(f"stage2_mesh_speedup_D{n_dev}", 0.0,
             f"{speedup:.2f}x overlapped vs serial")

    one_dev = [r for r in records
               if r["mode"] == "overlapped" and r["n_devices"] == 1]
    if one_dev:
        base = one_dev[0]["first_pass_bytes"]
        for r in records:
            if r["mode"] == "overlapped":
                emit(f"stage2_mesh_pass_bytes_D{r['n_devices']}", 0.0,
                     f"{r['first_pass_bytes'] / base:.2f}x the 1-device "
                     f"per-pass bytes")

    payload = {"benchmark": "stage2_mesh",
               "backend": jax.default_backend(),
               "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
               "provenance": provenance(),
               "problem": {"n": PROBLEM[0], "budget": PROBLEM[1],
                           "classes": PROBLEM[2], "max_epochs": PROBLEM[3],
                           "tile_rows": TILE},
               "records": records}
    with open(OUT_PATH, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {OUT_PATH} ({len(records)} records)", flush=True)


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
