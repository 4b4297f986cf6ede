"""FLOPs, bytes and peaks against hand-computed values at one small shape."""
import json

import pytest

from bench import work
from bench.job import JobRecord

V5E = work.peaks("TPU v5 lite")


def test_peaks_table():
    assert V5E["bf16_flops_per_s"] == 197e12
    assert V5E["int8_ops_per_s"] == 393e12
    assert V5E["hbm_bytes_per_s"] == 819e9
    with open(work.PEAKS) as f:
        assert "Google Cloud" in json.load(f)["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="TPU v9"):
        work.peaks("TPU v9")


def test_job_flops_by_hand():
    # n = 10 train rows, m = 4 test rows, p = 3, B = 5, rank 4, 2 tasks.
    cfg = {"train_rows": 10, "test_rows": 4, "features": 3, "budget": 5}
    rec = JobRecord(seconds=1, stage1_s=0, stage2_s=0, predict_s=0, rank=4,
                    epochs=[3, 2], task_rows=[6, 7])
    stage1 = 2 * 10 * 5 * 3 + 2 * 5 * 5 * 3 + 2 * 10 * 5 * 4       # 850
    stage2 = 4 * 4 * (3 * 6 + 2 * 7)                                # 512
    predict = 2 * 4 * 5 * 3 + 2 * 4 * 5 * 4 + 2 * 4 * 4 * 2          # 344
    assert work.row_visits(rec) == 32
    assert work.job_flops(rec, cfg) == stage1 + stage2 + predict == 1706
    rec.coord_visits = 20          # the streamed route counts its visits
    assert work.job_flops(rec, cfg) == 850 + 4 * 4 * 20 + 344


def test_smo_roofline_is_memory_bound():
    r, v = 2048, 1000
    assert work.smo_flops(r, v) == 8_192_000
    assert work.smo_bytes(r, v) == 2055 * 4 * 1000
    t, bound = work.roofline_seconds(work.smo_flops(r, v),
                                     work.smo_bytes(r, v), V5E)
    assert bound == "memory"
    assert t == pytest.approx(8_220_000 / 819e9)
    t, bound = work.roofline_seconds(197e12, 1.0, V5E)
    assert (t, bound) == (pytest.approx(1.0), "compute")
