"""Plain reference of one job: Nystrom factor, OVO tasks, KKT residual,
decision values and votes, written from the paper's description.

It imports nothing of the program and takes nothing the program made: the
landmark rows are drawn again from the seed by the same rule (uniform,
without replacement, `jax.random.choice` under `PRNGKey(seed)`), and every
matrix is built from the job's rows.  ``exact`` runs in float64 on the
host.  ``high`` is the control: the same reference in float32 with every
matrix product taken as three bf16 passes (the TPU's `Precision.HIGH`,
spelled out so that it means the same on any backend) and a float32
eigendecomposition, plus a plain coordinate-ascent solve in that
precision, so that it can stand in the program's place.
"""
from __future__ import annotations

import dataclasses
import itertools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

EIG_RTOL = 1e-6       # drop eigenvalues under this share of the largest
BLOCK_ROWS = 8192     # rows per block of every n x B product


def landmark_rows(n: int, budget: int, seed: int) -> np.ndarray:
    if budget >= n:
        return np.arange(n)
    key = jax.random.PRNGKey(seed)
    return np.asarray(jax.random.choice(key, n, shape=(budget,),
                                        replace=False))


def class_pairs(n_classes: int):
    return list(itertools.combinations(range(n_classes), 2))


def ovo_tasks(labels: np.ndarray, n_classes: int):
    """[(rows, signs)] per pair (a, b), a < b, rows ascending, a -> +1."""
    out = []
    for a, b in class_pairs(n_classes):
        rows = np.where((labels == a) | (labels == b))[0]
        out.append((rows, np.where(labels[rows] == a, 1.0, -1.0)))
    return out


def vote(decisions: np.ndarray, n_classes: int) -> np.ndarray:
    """Class index per row: the binary sign rule, or the one-vs-one
    majority vote with ties to the smaller class."""
    if n_classes == 2:
        return np.where(decisions[:, 0] > 0, 0, 1)
    pairs = class_pairs(n_classes)
    votes = np.zeros((decisions.shape[0], n_classes), np.int64)
    for t, (a, b) in enumerate(pairs):
        win = decisions[:, t] > 0
        votes[win, a] += 1
        votes[~win, b] += 1
    return np.argmax(votes, axis=1)


# -- float64 on the host ------------------------------------------------------

def _rbf64(a: np.ndarray, b: np.ndarray, gamma: float) -> np.ndarray:
    d2 = ((a * a).sum(1)[:, None] + (b * b).sum(1)[None, :]
          - 2.0 * (a @ b.T))
    return np.exp(-gamma * np.maximum(d2, 0.0))


@dataclasses.dataclass
class Factor:
    landmarks: np.ndarray     # (B, p)
    projector: np.ndarray     # (B, rank)
    rank: int

    def features(self, x: np.ndarray, gamma: float) -> np.ndarray:
        x = np.asarray(x, np.float64)
        return np.concatenate([
            _rbf64(x[s:s + BLOCK_ROWS], self.landmarks, gamma) @ self.projector
            for s in range(0, x.shape[0], BLOCK_ROWS)])


def _projector(k_mm, eigh):
    k_mm = 0.5 * (k_mm + k_mm.T)
    evals, evecs = eigh(k_mm)
    evals, evecs = evals[::-1], evecs[:, ::-1]
    rank = int(np.sum(evals > EIG_RTOL * max(float(evals[0]), 0.0)))
    return evecs[:, :rank] / np.sqrt(evals[:rank])[None, :], rank


def exact_factor(x: np.ndarray, budget: int, gamma: float,
                 seed: int) -> Factor:
    lm = np.asarray(x[landmark_rows(x.shape[0], budget, seed)], np.float64)
    proj, rank = _projector(_rbf64(lm, lm, gamma), np.linalg.eigh)
    return Factor(lm, proj, rank)


def kkt_violation(G: np.ndarray, rows: np.ndarray, signs: np.ndarray,
                  alpha: np.ndarray, C: float) -> float:
    """Largest projected-gradient KKT violation of ``alpha`` for one task
    of the dual  max 1'a - a'(YGG'Y)a/2,  0 <= a <= C,  evaluated with G;
    a value outside the box counts by how far it lies outside, over C."""
    g_rows = G[rows]
    w = g_rows.T @ (alpha * signs)
    grad = 1.0 - signs * (g_rows @ w)
    pg = np.where(alpha <= 0.0, np.maximum(grad, 0.0),
                  np.where(alpha >= C, np.minimum(grad, 0.0), grad))
    outside = np.maximum(np.maximum(-alpha, alpha - C), 0.0) / C
    return float(max(np.max(np.abs(pg)), np.max(outside)))


# -- the control: float32 with three bf16 passes per product ------------------

def _split(a):
    hi = a.astype(jnp.bfloat16)
    return hi, (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def dot_high(a, b):
    """a @ b as three bf16 passes accumulated in float32."""
    (ah, al), (bh, bl) = _split(a), _split(b)
    d = partial(jnp.dot, preferred_element_type=jnp.float32)
    return d(ah, bh) + (d(ah, bl) + d(al, bh))


@jax.jit
def _rbf_high(a, b, gamma):
    d2 = ((a * a).sum(1)[:, None] + (b * b).sum(1)[None, :]
          - 2.0 * dot_high(a, b.T))
    return jnp.exp(-gamma * jnp.maximum(d2, 0.0))


@jax.jit
def _features_high(x, lm, proj, gamma):
    return dot_high(_rbf_high(x, lm, gamma), proj)


def high_factor(x: np.ndarray, budget: int, gamma: float, seed: int):
    lm = jnp.asarray(x[landmark_rows(x.shape[0], budget, seed)], jnp.float32)
    g = jnp.float32(gamma)
    k_mm = np.asarray(_rbf_high(lm, lm, g))
    proj, rank = _projector(k_mm, np.linalg.eigh)     # float32 LAPACK
    proj = jnp.asarray(np.ascontiguousarray(proj), jnp.float32)
    feats = lambda rows: np.concatenate([
        np.asarray(_features_high(jnp.asarray(rows[s:s + BLOCK_ROWS]), lm,
                                  proj, g))
        for s in range(0, rows.shape[0], BLOCK_ROWS)])
    return feats, rank


@partial(jax.jit, static_argnames=("tol", "max_epochs"))
def _cd_high(G, idx, y, c, tol: float, max_epochs: int):
    """Plain dual coordinate ascent, every epoch a full pass in row order,
    until a pass sees no KKT violation of ``tol`` or more (float32, the
    w.row products as three bf16 passes)."""
    q = jnp.sum(G[idx] ** 2, axis=1)

    def epoch(alpha, w):
        def body(i, s):
            alpha, w, viol = s
            row = G[idx[i]]
            grad = 1.0 - y[i] * dot_high(w[None, :], row[:, None])[0, 0]
            a = alpha[i]
            pg = jnp.where(a <= 0.0, jnp.maximum(grad, 0.0),
                           jnp.where(a >= c[i], jnp.minimum(grad, 0.0), grad))
            a_new = jnp.clip(a + grad / jnp.maximum(q[i], 1e-12), 0.0, c[i])
            live = c[i] > 0.0
            a_new = jnp.where(live, a_new, a)
            w = w + ((a_new - a) * y[i]) * row
            viol = jnp.where(live, jnp.maximum(viol, jnp.abs(pg)), viol)
            return alpha.at[i].set(a_new), w, viol
        return jax.lax.fori_loop(0, idx.shape[0], body,
                                 (alpha, w, jnp.float32(0.0)))

    def cond(s):
        return jnp.logical_and(s[2] >= tol, s[3] < max_epochs)

    def step(s):
        alpha, w, _, k = s
        alpha, w, viol = epoch(alpha, w)
        return alpha, w, viol, k + 1

    alpha0 = jnp.zeros(idx.shape, jnp.float32)
    w0 = jnp.zeros((G.shape[1],), jnp.float32)
    alpha, w, _, _ = jax.lax.while_loop(
        cond, step, (alpha0, w0, jnp.float32(jnp.inf), jnp.int32(0)))
    return alpha, w


def high_solve(G: np.ndarray, tasks, C: float, tol: float, max_epochs: int):
    """Per task (alpha over its rows, w) by `_cd_high`, tasks padded alike
    with inert rows (c = 0) and vmapped."""
    n_pad = max(len(r) for r, _ in tasks)
    T = len(tasks)
    idx = np.zeros((T, n_pad), np.int32)
    y = np.ones((T, n_pad), np.float32)
    c = np.zeros((T, n_pad), np.float32)
    for t, (rows, signs) in enumerate(tasks):
        idx[t, :len(rows)], y[t, :len(rows)], c[t, :len(rows)] = rows, signs, C
    solve = jax.vmap(partial(_cd_high, tol=tol, max_epochs=max_epochs),
                     in_axes=(None, 0, 0, 0))
    alpha, w = solve(jnp.asarray(G, jnp.float32), jnp.asarray(idx),
                     jnp.asarray(y), jnp.asarray(c))
    alpha = np.asarray(alpha)
    return [alpha[t, :len(r)] for t, (r, _) in enumerate(tasks)], np.asarray(w)
