"""Shrinking with *bucket compaction* — the production stage-2 driver.

In the paper, shrinking is "a complete game-changer" (x220 / x350 on the SMO
phase) partly because "after removing many variables ... the memory demand for
the relevant sub-matrix of G reduces and the processor cache becomes more
effective".  A masked-out variable in a fixed-shape JAX loop costs as much as
an active one, so to realize the paper's win we physically COMPACT the active
rows into the smallest power-of-two bucket after every full pass:

  * epochs stream only `bucket >= n_active` rows of G (HBM traffic drops
    proportionally — the TPU version of "the cache becomes more effective");
  * bucket sizes halve from n, so at most log2(n / tile) distinct kernel
    shapes ever compile;
  * every `full_pass_period`-th epoch runs un-compacted over ALL rows, which
    re-activates violating variables and provides the convergence check — the
    paper's eta ~ 5% re-check budget.

The epoch itself is the Pallas SMO kernel (kernels/smo.py) or its jnp oracle.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.core.dual_solver import SolverConfig
from repro.core.kernel_fn import HIGHEST


@dataclasses.dataclass
class CompactStats:
    epochs: int = 0
    full_passes: int = 0
    final_violation: float = float("inf")
    active_history: List[int] = dataclasses.field(default_factory=list)
    rows_streamed: int = 0           # sum of bucket sizes over epochs
    seconds: float = 0.0


def _bucket(n_active: int, n: int, tile: int) -> int:
    """Smallest power-of-two multiple of `tile` covering n_active (<= n)."""
    b = tile
    while b < n_active:
        b *= 2
    return min(b, n)


def solve_compact(
    G_rows: jnp.ndarray,
    y: jnp.ndarray,
    c: jnp.ndarray,
    config: SolverConfig = SolverConfig(),
    *,
    epoch_fn: Optional[Callable] = None,
    alpha0: Optional[jnp.ndarray] = None,
    tile: int = 256,
):
    """Solve one binary task on its dense row matrix (n, B).

    Returns (alpha, w, CompactStats).  `epoch_fn` defaults to the Pallas SMO
    kernel wrapper (interpret mode off-TPU); pass `kernels.ref`-based callables
    to run the oracle.
    """
    if epoch_fn is None:
        from repro.kernels.ops import smo_epoch as epoch_fn  # lazy import
    t0 = time.perf_counter()
    n, B = G_rows.shape
    tile = min(tile, n)
    y = jnp.asarray(y, jnp.float32)
    c = jnp.asarray(c, jnp.float32)
    q = jnp.sum(G_rows * G_rows, axis=1)
    alpha = (jnp.zeros((n,), jnp.float32) if alpha0 is None
             else jnp.clip(jnp.asarray(alpha0, jnp.float32), 0.0, c))
    w = jnp.dot(alpha * y, G_rows, precision=HIGHEST)
    unchanged = jnp.zeros((n,), jnp.int32)

    period = config.full_pass_period if config.shrink else 1
    shrink_k = config.shrink_k if config.shrink else 1 << 30
    stats = CompactStats()
    cur: Optional[np.ndarray] = None          # active row indices (host)
    sub = None                                # compacted device arrays

    for epoch in range(config.max_epochs):
        full = (epoch % period == 0) or not config.shrink
        if full:
            if cur is not None and sub is not None:
                # scatter compacted state back before the full pass
                a_s, u_s = sub
                alpha = alpha.at[cur].set(a_s[: len(cur)])
                unchanged = unchanged.at[cur].set(u_s[: len(cur)])
                cur, sub = None, None
            alpha, unchanged, w, viol = epoch_fn(
                G_rows, y, c, q, alpha, unchanged, w,
                full_pass=True, shrink_k=shrink_k)
            stats.full_passes += 1
            stats.rows_streamed += n
            viol = float(viol)
            stats.final_violation = viol
            stats.active_history.append(n)
            if viol < config.tol:
                stats.epochs = epoch + 1
                break
            # compact for the cheap epochs
            u_host = np.asarray(unchanged)
            act = np.where((u_host < shrink_k) & (np.asarray(c) > 0))[0]
            if config.shrink and len(act) > 0:
                b = _bucket(len(act), n, tile)
                if b < n:
                    pad = np.zeros(b - len(act), dtype=np.int64)
                    cur_full = np.concatenate([act, pad])  # pad rows inert via c
                    cmask = np.zeros(b, np.float32)
                    cmask[: len(act)] = np.asarray(c)[act]
                    cur = act
                    sub = (alpha[cur_full].at[len(act):].set(0.0),
                           unchanged[cur_full])
                    G_sub = G_rows[cur_full]
                    y_sub = y[cur_full]
                    q_sub = q[cur_full]
                    c_sub = jnp.asarray(cmask)
        else:
            if cur is not None and sub is not None:
                a_s, u_s = sub
                a_s, u_s, w, viol = epoch_fn(
                    G_sub, y_sub, c_sub, q_sub, a_s, u_s, w,
                    full_pass=False, shrink_k=shrink_k)
                sub = (a_s, u_s)
                stats.rows_streamed += int(G_sub.shape[0])
                stats.active_history.append(int(G_sub.shape[0]))
            else:
                alpha, unchanged, w, viol = epoch_fn(
                    G_rows, y, c, q, alpha, unchanged, w,
                    full_pass=False, shrink_k=shrink_k)
                stats.rows_streamed += n
                stats.active_history.append(n)
        stats.epochs = epoch + 1

    if cur is not None and sub is not None:
        a_s, u_s = sub
        alpha = alpha.at[cur].set(a_s[: len(cur)])
    stats.seconds = time.perf_counter() - t0
    return alpha, w, stats
