"""Operations and bytes of a job, from its shapes and its counts.

The model FLOPs of one job (the eigendecomposition is not counted):

  stage 1   2 n B p  (K_nm)  +  2 B^2 p  (K_mm)  +  2 n B r  (G = K_nm P)
  stage 2   4 r v    (per row visit: the w.g dot and the rank-1 update)
  predict   2 m B p  +  2 m B r  +  2 m r T

with n training rows, m test rows, p features, B landmarks, r the rank,
T tasks and v the rows the stage-2 route sweeps: every real row of every
task in every epoch in HBM, `coord_visits` on the streamed route.

The SMO kernel per real row visit reads the row of G and five per-row
values and writes two: (r + 7) * 4 bytes, and does 4 r FLOPs.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str, path: str = PEAKS) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind raises."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def stage1_flops(n: int, B: int, p: int, r: int) -> float:
    return 2.0 * n * B * p + 2.0 * B * B * p + 2.0 * n * B * r


def stage2_flops(r: int, visits: int) -> float:
    return 4.0 * r * visits


def predict_flops(m: int, B: int, p: int, r: int, T: int) -> float:
    return 2.0 * m * B * p + 2.0 * m * B * r + 2.0 * m * r * T


def row_visits(rec) -> int:
    """Rows the stage-2 route swept in one job (see the module docstring)."""
    if rec.coord_visits:
        return rec.coord_visits
    return sum(e * k for e, k in zip(rec.epochs, rec.task_rows))


def job_flops(rec, cfg: dict) -> float:
    n, m, p, B = (cfg["train_rows"], cfg["test_rows"], cfg["features"],
                  cfg["budget"])
    return (stage1_flops(n, B, p, rec.rank)
            + stage2_flops(rec.rank, row_visits(rec))
            + predict_flops(m, B, p, rec.rank, len(rec.epochs)))


def smo_flops(r: int, visits: int) -> float:
    return 4.0 * r * visits


def smo_bytes(r: int, visits: int) -> float:
    return (r + 7) * 4.0 * visits


def roofline_seconds(flops: float, nbytes: float, peak: dict):
    """(least seconds the chip could take, the bound that sets it)."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "memory") if t_bytes >= t_flops else (t_flops, "compute")
