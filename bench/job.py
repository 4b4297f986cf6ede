"""One job of a cell, through the program's public API: a new `LPDSVM`
with the configuration's options and the traffic's route, `fit` (stage 1,
then stage 2), `decision_function` and `predict` on the test rows.

Every job of a run is the same job: the same rows and the same landmark
key.  Nothing but compiled programs carries from one job to the next.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List

import numpy as np

from bench.check import Answers

# Route of each traffic mix, as `FitStats` reports it:
# (stage1_streamed, stage2_streamed).
ROUTES = {"hbm": (False, False), "stream": (True, True)}


@dataclasses.dataclass
class JobRecord:
    seconds: float            # host clock around the whole job
    stage1_s: float           # FitStats.stage1_seconds
    stage2_s: float           # FitStats.stage2_seconds
    predict_s: float          # decision_function + predict
    rank: int
    epochs: List[int]         # per task
    task_rows: List[int]      # real rows per task
    kernel_calls: int = 0     # Stage2StreamStats (streamed stage 2 only)
    coord_visits: int = 0
    tile_rows: int = 0


def device_budget(cfg: dict, traffic: dict) -> int:
    """The streamed route's device budget: the usable HBM scaled by the
    share of the published rows that the cell trains on, so that G stands
    to the budget as the full job's G stands to the chip."""
    share = cfg["train_rows"] / cfg["published"]["train_rows"]
    return int(traffic["usable_hbm_bytes"] * share)


def make_svm(cfg: dict, traffic: dict, gamma: float, landmark_seed: int):
    from repro.core import KernelParams, LPDSVM, StreamConfig
    kw = {}
    if traffic["route"] == "stream":
        kw["stream_config"] = StreamConfig(
            device_budget_bytes=device_budget(cfg, traffic),
            block_dtype=traffic["block_dtype"],
            stage1_dtype=traffic["stage1_dtype"])
    return LPDSVM(KernelParams("rbf", gamma=gamma), C=cfg["C"],
                  budget=cfg["budget"], tol=cfg["tol"],
                  max_epochs=cfg["max_epochs"], seed=landmark_seed, **kw)


def run_job(cfg, traffic, gamma, landmark_seed, x, y, x_test, span):
    """One job; returns (JobRecord, the fitted estimator, decisions,
    labels).  ``span(name)`` is a context manager around each stage."""
    t0 = time.perf_counter()
    svm = make_svm(cfg, traffic, gamma, landmark_seed)
    with span("stage1"):
        svm.prepare(x)
    with span("stage2"):
        svm.fit(x, y)
    t1 = time.perf_counter()
    with span("predict"):
        decisions = svm.decision_function(x_test)
        labels = svm.predict(x_test)
    t2 = time.perf_counter()
    st = svm.stats
    route = (st.stage1_streamed, st.stage2_streamed)
    if route != ROUTES[traffic["route"]]:
        raise RuntimeError(
            f"route {traffic['route']!r} expects (stage1_streamed, "
            f"stage2_streamed) = {ROUTES[traffic['route']]}, the fit took "
            f"{route}")
    c = np.asarray(svm.tasks_.c)
    rec = JobRecord(seconds=t2 - t0, stage1_s=st.stage1_seconds,
                    stage2_s=st.stage2_seconds, predict_s=t2 - t1,
                    rank=int(st.effective_rank),
                    epochs=[int(e) for e in np.asarray(st.epochs)],
                    task_rows=[int(k) for k in (c > 0).sum(axis=1)])
    if st.stage2_stats is not None:
        s2 = st.stage2_stats
        rec.kernel_calls, rec.coord_visits, rec.tile_rows = (
            int(s2.kernel_calls), int(s2.coord_visits), int(s2.tile_rows))
    return rec, svm, np.asarray(decisions), np.asarray(labels)


def answers(svm, decisions, labels, sample) -> Answers:
    """What the job answered: each task's alpha spread over the training
    rows through the program's own task rows, G at the sampled rows, the
    decision values and the labels as class indices."""
    idx = np.asarray(svm.tasks_.idx)
    live = np.asarray(svm.tasks_.c) > 0
    alpha = np.asarray(svm.alpha_)
    dense = np.zeros((idx.shape[0], int(svm.factor.G.shape[0])), np.float32)
    for t in range(idx.shape[0]):
        dense[t, idx[t][live[t]]] = alpha[t][live[t]]
    classes = np.asarray(svm.classes_)
    return Answers(rank=int(svm.factor.effective_rank),
                   g_sample=np.asarray(svm.factor.G[sample]),
                   alpha=dense, decisions=decisions,
                   labels=np.searchsorted(classes, labels))
