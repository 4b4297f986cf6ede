"""Each cell run end to end on the CPU at a tiny size, through the same
set-up, window and check as on the chip (`run.run_cell`, which skips only
the look for a TPU): the sound program passes the comparison, and the
program broken underneath fails it, once per fault a cell can have."""
import jax
import numpy as np
import pytest

from bench import run

TINY = {
    "mnist8m.hbm": ({"train_rows": 600, "test_rows": 200, "budget": 64},
                    None),
    # The budget scaled down so that both stages still stream at 2,048 rows.
    "susy.stream": ({"train_rows": 2048, "test_rows": 256, "budget": 128},
                    {"usable_hbm_bytes": 240_000_000}),
}


def run_tiny(cell, seed=2 ** 31 + 12345):
    cfg, traffic = TINY[cell]
    return run.run_cell(cell, seed, 0.0, False, jax.devices()[:1],
                        config_overrides=cfg, traffic_overrides=traffic)


@pytest.mark.parametrize("cell", sorted(TINY))
def test_sound_job_is_correct(cell):
    out = run_tiny(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 1 and out["failed"] == 0
    assert out["window_compiles"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) >= {"job_s", "setup_s"}


def _unchanged(monkeypatch):
    """Stage 2 hands back its initial state: alpha0 and w = 0."""
    from repro.core import svm
    solve = svm.LPDSVM._solve_stage2

    def fault(self, tasks, trace=None):
        res = solve(self, tasks, trace=trace)
        return res._replace(alpha=tasks.alpha0, w=np.zeros_like(res.w))
    monkeypatch.setattr(svm.LPDSVM, "_solve_stage2", fault)


def _half_rows(monkeypatch):
    """Stage 2 solves each task over its first half of rows only."""
    from repro.core import svm
    build = svm.build_ovo_tasks

    def fault(labels, n_classes, C, **kw):
        tasks, pairs = build(labels, n_classes, C, **kw)
        c = np.asarray(tasks.c).copy()
        for t in range(c.shape[0]):
            live = np.flatnonzero(c[t] > 0)
            c[t, live[len(live) // 2:]] = 0.0
        return tasks._replace(c=c), pairs
    monkeypatch.setattr(svm, "build_ovo_tasks", fault)


def _decision(monkeypatch):
    """One decision value of the first task comes out with its sign
    flipped and doubled."""
    from repro.core import svm
    decide = svm.ovo_decision_values

    def fault(features, W):
        d = np.array(decide(features, W))
        d[0, 0] = -2.0 * d[0, 0]
        return d
    monkeypatch.setattr(svm, "ovo_decision_values", fault)


def _label(monkeypatch):
    """One predicted label is replaced by another class."""
    from repro.core import svm
    vote = svm.LPDSVM._vote

    def fault(self, d):
        out = np.array(vote(self, d))
        out[0] = self.classes_[(np.searchsorted(self.classes_, out[0]) + 1)
                               % len(self.classes_)]
        return out
    monkeypatch.setattr(svm.LPDSVM, "_vote", fault)


def _factor(monkeypatch):
    """Stage 1's G comes out with its first column doubled."""
    from repro.core import svm
    compute = svm.compute_factor

    def fault(*a, **kw):
        f = compute(*a, **kw)
        G = f.G
        f.G = (G.at[:, 0].multiply(2.0) if hasattr(G, "at")
               else np.concatenate([2.0 * G[:, :1], G[:, 1:]], axis=1))
        return f
    monkeypatch.setattr(svm, "compute_factor", fault)


FAULTS = {"state_unchanged": _unchanged, "half_rows": _half_rows,
          "decision_altered": _decision, "label_altered": _label,
          "factor_altered": _factor}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", sorted(TINY))
def test_broken_program_is_not_correct(cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = run_tiny(cell)
    assert not out["correct"]
    assert out["failed"] == out["attempted"] == 1
