"""Stage 2 of LPD-SVM: dual coordinate ascent on the precomputed factor G.

With the approximate kernel GG^T, the dual SVM problem

    max_{alpha in [0,C]^n}  1^T alpha - 1/2 alpha^T (Y GG^T Y) alpha

is exactly a *linear* SVM whose data points are the rows of G (paper, sec. 4).
The solver below is a LIBLINEAR-style dual coordinate ascent with:

  * truncated Newton coordinate steps
        alpha_i <- clip(alpha_i + (1 - y_i <w, g_i>) / <g_i, g_i>, 0, C)
    while maintaining w = sum_i alpha_i y_i g_i in R^B (iteration cost O(B));
  * the paper's simplistic-but-robust shrinking: a variable whose value did not
    change for `shrink_k = 5` consecutive touches is deactivated, and every
    `full_pass_period = 20`-th epoch (= the eta ~ 5% compute fraction) is a full
    pass over ALL variables that re-activates any variable with a KKT violation;
  * an adaptive stopping criterion: converge when a *full* pass observes a
    maximum projected-gradient KKT violation below `tol` (LIBLINEAR-style);
  * warm starts: `alpha0` seeds the solve (used across the C grid).

Tasks are described by index vectors into the shared G so that one-vs-one /
cross-validation / grid tasks never copy G.  Padding rows carry c = 0, which
pins alpha = 0 and makes them inert.  Everything is jit- and vmap-compatible;
`solve_batch` is the building block the distributed task farm shards.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.kernel_fn import HIGHEST

DELTA_EPS = 0.0   # "did not change": exact in float (bound hits are exact clips)
Q_FLOOR = 1e-12   # guards division for zero rows (padding)


@dataclasses.dataclass(frozen=True)
class SolverConfig:
    tol: float = 0.1               # max KKT violation on a full pass
    max_epochs: int = 1000
    shrink_k: int = 5              # paper: k = 5 consecutive no-change touches
    full_pass_period: int = 20     # paper: eta ~ 5% -> every 20th epoch is full
    shrink: bool = True


class TaskBatch(NamedTuple):
    """A batch of binary SVM tasks over a shared factor G (leading task axis)."""

    idx: jnp.ndarray     # (T, n_pad) int32 rows of G
    y: jnp.ndarray       # (T, n_pad) float32 in {-1, +1} (padding value is free)
    c: jnp.ndarray       # (T, n_pad) float32 box bound; 0 for padding -> inert
    alpha0: jnp.ndarray  # (T, n_pad) warm start

    @property
    def n_tasks(self) -> int:
        return self.idx.shape[0]


class SolveResult(NamedTuple):
    alpha: jnp.ndarray          # (T, n_pad)
    w: jnp.ndarray              # (T, B) primal weight in the low-rank space
    epochs: jnp.ndarray         # (T,) epochs consumed
    violation: jnp.ndarray      # (T,) max KKT violation at the last full pass
    dual_obj: jnp.ndarray       # (T,)
    n_sv: jnp.ndarray           # (T,) support-vector count


def _projected_gradient(g, alpha, c):
    """KKT violation of coordinate i: projected dual gradient for box [0, c]."""
    at_lo = alpha <= 0.0
    at_hi = alpha >= c
    pg = jnp.where(at_lo, jnp.maximum(g, 0.0), jnp.where(at_hi, jnp.minimum(g, 0.0), g))
    return jnp.where(c > 0.0, pg, 0.0)   # padding never violates


def epoch_ref(G, idx, y, c, q, alpha, w, unchanged, shrink_k, full_pass):
    """One sequential coordinate-ascent epoch (pure-jnp oracle for the Pallas
    SMO kernel; also the path used inside jit/vmap).

    Returns (alpha, w, unchanged, max_violation_seen).
    """
    n_pad = idx.shape[0]

    def body(i, state):
        alpha, w, unchanged, viol = state
        row = G[idx[i]]
        a_i, c_i, y_i, q_i = alpha[i], c[i], y[i], q[i]
        active = jnp.logical_and(
            c_i > 0.0, jnp.logical_or(full_pass, unchanged[i] < shrink_k))
        g = 1.0 - y_i * jnp.dot(w, row, precision=HIGHEST)
        pg = _projected_gradient(g, a_i, c_i)
        a_new = jnp.clip(a_i + g / jnp.maximum(q_i, Q_FLOOR), 0.0, c_i)
        a_new = jnp.where(active, a_new, a_i)
        delta = a_new - a_i
        w = w + (delta * y_i) * row
        alpha = alpha.at[i].set(a_new)
        changed = jnp.abs(delta) > DELTA_EPS
        # A full pass touches every variable, so a shrunk-but-violating variable
        # changes there and is re-activated (unchanged -> 0): the paper's
        # "dedicate a fraction of compute to re-checking removed variables".
        u_new = jnp.where(changed, 0, unchanged[i] + 1)
        u_new = jnp.where(active, u_new, unchanged[i])
        unchanged = unchanged.at[i].set(u_new)
        viol = jnp.where(active, jnp.maximum(viol, jnp.abs(pg)), viol)
        return alpha, w, unchanged, viol

    return jax.lax.fori_loop(0, n_pad, body, (alpha, w, unchanged, jnp.float32(0.0)))


def _init_w(G, idx, y, alpha0):
    rows = G[idx]                                   # (n_pad, B)
    return jnp.dot(alpha0 * y, rows, precision=HIGHEST)


@partial(jax.jit, static_argnames=("config",))
def solve_one(G, idx, y, c, alpha0, config: SolverConfig) -> SolveResult:
    """Solve a single binary task to convergence (while_loop over epochs)."""
    n_pad = idx.shape[0]
    rows_q = jnp.sum(G[idx] ** 2, axis=-1)          # q_ii = <g_i, g_i>
    w0 = _init_w(G, idx, y, alpha0)
    unchanged0 = jnp.zeros((n_pad,), dtype=jnp.int32)
    period = config.full_pass_period if config.shrink else 1
    shrink_k = config.shrink_k if config.shrink else jnp.iinfo(jnp.int32).max

    def cond(state):
        _, _, epoch, done = state
        return jnp.logical_and(~done, epoch < config.max_epochs)

    def body(state):
        (alpha, w, unchanged), viol_last, epoch, _ = state
        full_pass = (epoch % period) == 0
        alpha, w, unchanged, viol = epoch_ref(
            G, idx, y, c, rows_q, alpha, w, unchanged, shrink_k, full_pass)
        done = jnp.logical_and(full_pass, viol < config.tol)
        viol_rec = jnp.where(full_pass, viol, viol_last)
        return ((alpha, w, unchanged), viol_rec, epoch + 1, done)

    init = ((alpha0, w0, unchanged0), jnp.float32(jnp.inf), jnp.int32(0),
            jnp.bool_(False))
    (alpha, w, _), viol, epochs, _ = jax.lax.while_loop(cond, body, init)
    dual = jnp.sum(alpha) - 0.5 * jnp.dot(w, w, precision=HIGHEST)
    n_sv = jnp.sum(alpha > 0.0)
    return SolveResult(alpha, w, epochs, viol, dual, n_sv)


@partial(jax.jit, static_argnames=("config",))
def solve_batch(G, tasks: TaskBatch, config: SolverConfig) -> SolveResult:
    """vmap of `solve_one` over the task axis (shared G)."""
    fn = lambda idx, y, c, a0: solve_one(G, idx, y, c, a0, config)
    return jax.vmap(fn)(tasks.idx, tasks.y, tasks.c, tasks.alpha0)


# ----------------------------------------------------------------------------
# objective helpers (tests / benchmarks)
# ----------------------------------------------------------------------------

def dual_objective(G, idx, y, alpha):
    w = _init_w(G, idx, y, alpha)
    return jnp.sum(alpha) - 0.5 * jnp.dot(w, w, precision=HIGHEST)


def primal_objective(G, idx, y, c, w):
    """P(w) = lambda/2 ||w||^2 + 1/n sum hinge, with lambda = 1/(C n).

    Uses the *box* c to identify real examples (c > 0) and the common C
    (assumed constant across real examples of the task).
    """
    real = c > 0.0
    n = jnp.sum(real)
    C = jnp.max(c)
    lam = 1.0 / (C * n)
    margins = y * jnp.dot(G[idx], w, precision=HIGHEST)
    hinge = jnp.where(real, jnp.maximum(0.0, 1.0 - margins), 0.0)
    # rescale to the dual's units: dual D corresponds to primal C * sum hinge
    return 0.5 * jnp.dot(w, w, precision=HIGHEST) + C * jnp.sum(hinge), lam, n


def duality_gap(G, idx, y, c, alpha):
    w = _init_w(G, idx, y, alpha)
    p, _, _ = primal_objective(G, idx, y, c, w)
    d = jnp.sum(alpha) - 0.5 * jnp.dot(w, w, precision=HIGHEST)
    return p - d
