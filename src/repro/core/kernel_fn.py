"""Kernel functions for LPD-SVM.

The paper uses the Gaussian kernel in all experiments; polynomial / tanh /
linear are supported since the solver only needs *batch* kernel evaluations
(sec. 4, "batch kernel computation ... matrix-matrix multiplication at their
core").  All kernels reduce to a blocked X @ Z.T plus an elementwise epilogue,
which is exactly what the Pallas gram kernel implements on TPU.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

KERNELS = ("rbf", "linear", "poly", "tanh")

# Every f32 matmul of the fit and of prediction asks for full f32 precision.
# A TPU's default takes a single bf16 pass; the RBF distance
# |x|^2 + |z|^2 - 2 x.z and the lambda^-1/2 whitening of the Nystrom
# projector amplify that rounding (on a v5e, G came out 42% of its RMS away
# from an f32 recompute at the default, 0.03% at HIGHEST).
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class KernelParams:
    """Hyperparameters of a kernel function (hashable, jit-static)."""

    kind: str = "rbf"
    gamma: float = 1.0     # rbf / poly / tanh scale
    coef0: float = 0.0     # poly / tanh offset
    degree: int = 3        # poly

    def __post_init__(self):
        if self.kind not in KERNELS:
            raise ValueError(f"unknown kernel {self.kind!r}; expected one of {KERNELS}")


def apply_epilogue(dot: jnp.ndarray, x_sq: jnp.ndarray, z_sq: jnp.ndarray,
                   params: KernelParams) -> jnp.ndarray:
    """Turn a block of inner products into kernel values.

    dot:  (n, m) block of <x_i, z_j>
    x_sq: (n,)  squared norms of the x rows
    z_sq: (m,)  squared norms of the z rows
    """
    if params.kind == "linear":
        return dot
    if params.kind == "rbf":
        d2 = x_sq[:, None] + z_sq[None, :] - 2.0 * dot
        d2 = jnp.maximum(d2, 0.0)  # numerical floor
        return jnp.exp(-params.gamma * d2)
    if params.kind == "poly":
        return (params.gamma * dot + params.coef0) ** params.degree
    if params.kind == "tanh":
        return jnp.tanh(params.gamma * dot + params.coef0)
    raise ValueError(params.kind)


@partial(jax.jit, static_argnames=("params",))
def gram(x: jnp.ndarray, z: jnp.ndarray, params: KernelParams) -> jnp.ndarray:
    """Reference (pure jnp) batch kernel matrix  K[i, j] = k(x_i, z_j)."""
    x = x.astype(jnp.float32)
    z = z.astype(jnp.float32)
    dot = jnp.dot(x, z.T, precision=HIGHEST)
    x_sq = jnp.sum(x * x, axis=-1)
    z_sq = jnp.sum(z * z, axis=-1)
    return apply_epilogue(dot, x_sq, z_sq, params)


def median_gamma(x: np.ndarray, sample: int = 256, seed: int = 0) -> float:
    """Median-squared-distance heuristic: gamma = 1 / median ||x_i - x_j||^2
    over a random row subsample (host-side numpy — this is data inspection,
    not compute).  Random rows, not the head: real datasets are often
    label-sorted and a single-class prefix would bias the median."""
    x = np.asarray(x, np.float32)
    if x.shape[0] > sample:
        rows = np.random.default_rng(seed).choice(x.shape[0], sample,
                                                  replace=False)
        x = x[np.sort(rows)]
    d2 = ((x[:, None] - x[None]) ** 2).sum(-1)
    d2 = d2[d2 > 0]
    return float(1.0 / np.median(d2)) if d2.size else 1.0


def kernel_diag(x: jnp.ndarray, params: KernelParams) -> jnp.ndarray:
    """k(x_i, x_i) without forming the full matrix."""
    x_sq = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1)
    if params.kind == "linear":
        return x_sq
    if params.kind == "rbf":
        return jnp.ones_like(x_sq)
    if params.kind == "poly":
        return (params.gamma * x_sq + params.coef0) ** params.degree
    if params.kind == "tanh":
        return jnp.tanh(params.gamma * x_sq + params.coef0)
    raise ValueError(params.kind)
