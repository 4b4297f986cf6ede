"""The comparison that decides `correct`: each job's answers against the
float64 reference of the same job.

A job answers with its factor's rank, the rows of G at a sample of
training rows, every task's alpha, the test set's decision values and its
labels.  The numbers compared, each against the limit of its cell
(``bench/workloads/<cell>.json``):

  rank_gap        |rank - reference rank|                         (stage 1)
  kernel_gap      max |G_s G_s' - R_s R_s'| over the sampled rows, with R
                  the reference factor: G's basis is free, G G' is not
                                                                  (stage 1)
  kkt_violation   per task, the largest projected-gradient KKT violation
                  of the job's alpha in the reference factor, max over
                  tasks; an alpha outside [0, C] counts by how far, over C
                                                                  (stage 2)
  decision_gap    per task, max |d - d_ref| / RMS(d_ref) over the test
                  rows, with d_ref the reference's decision values for the
                  job's own alpha, max over tasks            (stage 2 W, predict)
  label_mismatch  test rows whose label is not the vote of the job's own
                  decision values                                 (predict)
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

from bench import reference as ref

SAMPLE_ROWS = 512


@dataclasses.dataclass
class Answers:
    rank: int
    g_sample: np.ndarray        # (SAMPLE_ROWS, rank) rows of G
    alpha: np.ndarray           # (tasks, training rows), 0 off the task
    decisions: np.ndarray       # (test rows, tasks)
    labels: np.ndarray          # (test rows,) class indices


@dataclasses.dataclass
class Reference:
    factor: ref.Factor
    G: np.ndarray               # (n, rank) float64
    F_test: np.ndarray          # (test rows, rank) float64
    tasks: list                 # [(rows, signs)] per pair
    n_classes: int
    C: float
    sample: np.ndarray          # sampled training rows, ascending

    @property
    def k_sample(self) -> np.ndarray:
        g = self.G[self.sample]
        return g @ g.T


def sample_rows(n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.sort(rng.choice(n, min(SAMPLE_ROWS, n), replace=False))


def build_reference(x, y, x_test, cfg: dict, gamma: float,
                    landmark_seed: int, seed: int) -> Reference:
    fac = ref.exact_factor(x, cfg["budget"], gamma, landmark_seed)
    return Reference(fac, fac.features(x, gamma), fac.features(x_test, gamma),
                     ref.ovo_tasks(np.asarray(y), cfg["classes"]),
                     cfg["classes"], float(cfg["C"]),
                     sample_rows(x.shape[0], seed))


def compare(ans: Answers, R: Reference) -> Dict[str, float]:
    g = np.asarray(ans.g_sample, np.float64)
    kernel_gap = float(np.max(np.abs(g @ g.T - R.k_sample)))
    kkt, dgap = 0.0, 0.0
    for t, (rows, signs) in enumerate(R.tasks):
        a = np.asarray(ans.alpha[t][rows], np.float64)
        kkt = max(kkt, ref.kkt_violation(R.G, rows, signs, a, R.C))
        d_ref = R.F_test @ (R.G[rows].T @ (a * signs))
        rms = float(np.sqrt(np.mean(d_ref ** 2)))
        gap = float(np.max(np.abs(ans.decisions[:, t] - d_ref)))
        dgap = max(dgap, gap / rms if rms > 0 else np.inf)
    labels = ref.vote(np.asarray(ans.decisions, np.float64), R.n_classes)
    return {
        "rank_gap": float(abs(ans.rank - R.factor.rank)),
        "kernel_gap": kernel_gap,
        "kkt_violation": kkt,
        "decision_gap": dgap,
        "label_mismatch": float(np.sum(labels != np.asarray(ans.labels))),
    }


def judge(readings: List[Dict[str, float]], limits: Dict[str, float]):
    """(worst reading per number, jobs failed): a job fails when any of
    its numbers is above its limit or not a number."""
    worst = {k: max(r[k] for r in readings) for k in limits}
    failed = sum(any(not r[k] <= lim for k, lim in limits.items())
                 for r in readings)
    return worst, failed
