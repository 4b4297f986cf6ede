"""One-versus-one multi-class handling (paper sec. 4, following LIBSVM).

"one-versus-one means that independent SVMs are trained to separate each pair
of classes ... creating independent sub-problems is a welcome opportunity for
parallelization."  Task construction is host-side numpy (it is index
bookkeeping, not compute); the resulting `TaskBatch` is solved by
`dual_solver.solve_batch` or the sharded task farm in `distributed.py`.

Convention (LIBSVM): for the pair (a, b) with a < b, class a maps to +1.
Prediction uses majority voting with ties broken towards the smaller class
index.
"""
from __future__ import annotations

import itertools
from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.dual_solver import TaskBatch
from repro.core.kernel_fn import HIGHEST


def class_pairs(n_classes: int) -> List[Tuple[int, int]]:
    return list(itertools.combinations(range(n_classes), 2))


def _pad_to(arr: np.ndarray, n_pad: int, fill) -> np.ndarray:
    out = np.full((n_pad,), fill, dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


def build_ovo_tasks(
    labels: np.ndarray,
    n_classes: int,
    C: float,
    *,
    include_mask: Optional[np.ndarray] = None,
    n_pad: Optional[int] = None,
    pad_multiple: int = 8,
    alpha0: Optional[Sequence[np.ndarray]] = None,
) -> Tuple[TaskBatch, List[Tuple[int, int]]]:
    """Build the padded one-vs-one task batch.

    labels:        (n,) integer class labels, referring to rows of the shared G
    include_mask:  optional (n,) bool — rows to use (CV training folds)
    n_pad:         pad every task to this many rows (default: max pair size,
                   rounded up to `pad_multiple`)
    alpha0:        optional warm starts, one (task_size,) array per pair
    """
    labels = np.asarray(labels)
    if include_mask is None:
        include_mask = np.ones(labels.shape[0], dtype=bool)
    pairs = class_pairs(n_classes)
    idx_list, y_list = [], []
    for a, b in pairs:
        sel = np.where(include_mask & ((labels == a) | (labels == b)))[0]
        idx_list.append(sel.astype(np.int32))
        y_list.append(np.where(labels[sel] == a, 1.0, -1.0).astype(np.float32))
    max_n = max((len(s) for s in idx_list), default=1)
    if n_pad is None:
        n_pad = -(-max_n // pad_multiple) * pad_multiple
    if max_n > n_pad:
        raise ValueError(f"n_pad={n_pad} smaller than largest pair ({max_n})")

    T = len(pairs)
    idx = np.zeros((T, n_pad), dtype=np.int32)
    y = np.ones((T, n_pad), dtype=np.float32)
    c = np.zeros((T, n_pad), dtype=np.float32)
    a0 = np.zeros((T, n_pad), dtype=np.float32)
    for t in range(T):
        m = len(idx_list[t])
        idx[t] = _pad_to(idx_list[t], n_pad, 0)
        y[t] = _pad_to(y_list[t], n_pad, 1.0)
        c[t, :m] = C
        if alpha0 is not None and alpha0[t] is not None:
            a0[t, :m] = np.clip(alpha0[t][:m], 0.0, C)

    return (
        TaskBatch(idx=jnp.asarray(idx), y=jnp.asarray(y), c=jnp.asarray(c),
                  alpha0=jnp.asarray(a0)),
        pairs,
    )


def ovo_decision_values(features: jnp.ndarray, W: jnp.ndarray) -> jnp.ndarray:
    """(m, B) features x (T, B) per-pair weights -> (m, T) decision values."""
    return jnp.dot(features, W.T, precision=HIGHEST)


def ovo_vote(decisions: np.ndarray, pairs: List[Tuple[int, int]],
             n_classes: int) -> np.ndarray:
    """Majority vote over pairwise decisions -> (m,) class predictions.

    Vectorised over pairs: one scatter-add into the (m, n_classes) vote
    table instead of a Python loop — the grid farm scores |gammas| x |Cs|
    cells per search, so prediction is on the measured path now.
    """
    decisions = np.asarray(decisions)
    m = decisions.shape[0]
    pa = np.asarray([p[0] for p in pairs], np.int64)
    pb = np.asarray([p[1] for p in pairs], np.int64)
    winner = np.where(decisions > 0, pa[None, :], pb[None, :])   # (m, T)
    votes = np.zeros((m, n_classes), dtype=np.int32)
    np.add.at(votes, (np.repeat(np.arange(m), len(pairs)), winner.ravel()), 1)
    # np.argmax breaks ties towards the smaller index (LIBSVM behaviour)
    return np.argmax(votes, axis=1)
