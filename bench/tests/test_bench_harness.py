"""BENCHMARK.json against the files it names, and the entry point's
refusal to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import run

ROOT = run.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    spec, wl, cfg, traffic, limits = run.cell(cell)
    assert wl["chips"] in (1, 4)
    assert {"train_rows", "test_rows", "features", "classes", "budget",
            "C", "tol", "max_epochs", "generator"} <= set(cfg)
    assert traffic["route"] in ("hbm", "stream")
    assert set(limits) == {"rank_gap", "kernel_gap", "kkt_violation",
                           "decision_gap", "label_mismatch"}


def test_configs_resolve_and_list_their_cuts():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/configs/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        for key, (published, ran) in cfg["reduced"].items():
            assert cfg[key] == ran and cfg["published"][key] == published


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_has_a_reader(metric):
    assert callable(run.metric_reader(metric))


def test_names_units_and_text_fields():
    names = ([m["name"] for m in METRICS] + CELLS
             + [c["name"] for c in SPEC["configs"]]
             + [w["traffic"] for w in SPEC["workloads"]])
    assert len(set(names) - {w["traffic"] for w in SPEC["workloads"]}) == \
        len(METRICS) + len(CELLS) + len(SPEC["configs"])
    for n in names:
        assert NAME.match(n), n
    for m in METRICS:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    texts = ([w["why"] for w in SPEC["workloads"]]
             + [c["why"] for c in SPEC["configs"]]
             + [c["source"] for c in SPEC["configs"]]
             + [m["layer"] for m in SPEC["per_layer"]])
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t, t
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_bounds():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])


def test_every_per_layer_metric_moves_what_its_cells_report():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert cell in moved.get("workloads", CELLS), (m["name"], cell)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_and_more(cell):
    e2e = [m["name"] for m in run.cell_metrics(SPEC, cell, trace=False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert run.cell_metrics(SPEC, cell, trace=True)


def _bench(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3000000000", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_to_run_without_a_tpu():
    out = _bench(ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _bench(str(tmp_path))
    assert out.returncode != 0
    assert out.stdout.strip() == ""
