"""Stage 1 of LPD-SVM: complete precomputation of the low-rank factor G.

Paper, sec. 4:
  * sample B landmark points (a random subset of the training set — Nyström);
  * eigendecompose the B x B landmark kernel matrix K_mm (NOT Cholesky — kernel
    matrices are routinely only *semi*-definite and Cholesky "regularly runs
    into numerical problems");
  * drop eigenvalues below a threshold close to machine precision times the
    largest eigenvalue — those subspaces carry mostly numerical noise, and
    dropping them adaptively reduces the effective dimension B' <= B;
  * fully precompute G = K_nm @ V @ diag(lambda^-1/2)  of shape (n, B') so that
    G @ G.T ~= K.  The whitening (the lambda^-1/2) comes "nearly for free".

Everything here is jit-compatible except the adaptive rank choice, which is a
*data-dependent shape*: we keep the full B columns and zero out dropped
directions, plus report the effective rank.  A `compact=True` path (host-side)
physically slices the factor for the production two-stage flow.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kernel_fn import HIGHEST, KernelParams, gram
from repro.core.trace import resolve as resolve_tracer

# float32 machine epsilon is ~1.19e-7; the paper drops eigenvalues "as soon as
# the eigenvalues fall below a threshold close to the machine precision times
# the largest eigenvalue".
DEFAULT_EIG_RTOL = 1e-6


@dataclasses.dataclass
class LowRankFactor:
    """The fully precomputed stage-1 artifact, shared across folds/grid/pairs."""

    G: jnp.ndarray                # (n, B') feature rows; GG^T ~= K
    landmarks: jnp.ndarray        # (B, p) landmark points
    projector: jnp.ndarray        # (B, B') V * lambda^{-1/2} : maps K_xm -> features
    eigvals: jnp.ndarray          # (B,) spectrum of K_mm (descending)
    effective_rank: int           # B' after eigenvalue dropping
    kernel: KernelParams
    streamed: bool = False        # True -> G is a host-resident numpy buffer
                                  # produced by the out-of-core chunked path
    stage1_stats: Optional[object] = None
                                  # streaming.Stage1StreamStats of the build
                                  # (chunk wire bytes / dtype / autotune)

    @property
    def n(self) -> int:
        return self.G.shape[0]

    @property
    def rank(self) -> int:
        return self.G.shape[1]

    def features(self, x: jnp.ndarray) -> jnp.ndarray:
        """Map new points into the low-rank feature space (prediction path)."""
        k_xm = gram(x, self.landmarks, self.kernel)
        return jnp.dot(k_xm, self.projector, precision=HIGHEST)


def wait_for_factor(G) -> None:
    """Block until a factor's G is ready: device arrays wait on the async
    dispatch queue, a streamed (host numpy) G is ready by construction."""
    if hasattr(G, "block_until_ready"):
        G.block_until_ready()


def select_landmarks(x: jnp.ndarray, budget: int, key: jax.Array) -> jnp.ndarray:
    """Uniform random landmark (Nyström) sample; the paper's choice.

    "we settle on a fixed (yet data dependent) feature space representation
    based on a random sample" — equivalent to projection-based budget
    maintenance with all projections precomputed.
    """
    n = x.shape[0]
    if budget >= n:
        return x
    idx = jax.random.choice(key, n, shape=(budget,), replace=False)
    return jnp.take(x, idx, axis=0)


@partial(jax.jit, static_argnames=("params",))
def _eig_projector(k_mm: jnp.ndarray, params: KernelParams, rtol: float):
    """eigh of K_mm -> (projector with dropped dirs zeroed, eigvals desc, rank)."""
    # Symmetrize: batch kernel evaluation is deterministic but accumulate order
    # can differ between the two triangles on real hardware.
    k_mm = 0.5 * (k_mm + k_mm.T)
    evals, evecs = jnp.linalg.eigh(k_mm)           # ascending
    evals = evals[::-1]
    evecs = evecs[:, ::-1]
    lam_max = jnp.maximum(evals[0], 0.0)
    keep = evals > rtol * lam_max                  # adaptive rank
    inv_sqrt = jnp.where(keep, 1.0 / jnp.sqrt(jnp.where(keep, evals, 1.0)), 0.0)
    projector = evecs * inv_sqrt[None, :]          # (B, B), dropped cols zeroed
    return projector, evals, jnp.sum(keep)


def compute_factor(
    x: jnp.ndarray,
    params: KernelParams,
    budget: int,
    *,
    key: Optional[jax.Array] = None,
    eig_rtol: float = DEFAULT_EIG_RTOL,
    compact: bool = True,
    block_rows: int = 65536,
    gram_fn=gram,
    stream: Optional[bool] = None,
    stream_config=None,
) -> LowRankFactor:
    """Run stage 1: landmarks -> K_mm -> eigh (+drop) -> G = K_nm @ projector.

    ``gram_fn`` is injectable so the Pallas TPU gram kernel (kernels/ops.py)
    can replace the pure-jnp reference; both satisfy gram(x, z, params).
    ``block_rows`` streams K_nm row-blocks so the (n, B) intermediate never
    coexists with a second (n, B) temporary — the paper's "streaming fashion"
    requirement for G bigger than GPU memory.

    Out-of-core routing: ``stream=True`` forces the chunked host-resident
    pipeline (`core/streaming.py`); ``stream=None`` with a ``stream_config``
    auto-routes when the monolithic working set exceeds the config's device
    budget; ``stream=False`` (or no config) keeps the device-resident path.
    """
    from repro.core import streaming as _streaming

    if key is None:
        key = jax.random.PRNGKey(0)

    if not hasattr(x, "shape"):
        x = np.asarray(x, np.float32)
    n, p = x.shape
    if stream is None and stream_config is not None:
        stream = _streaming.should_stream(n, p, min(budget, n), stream_config)
    if stream:
        cfg = stream_config or _streaming.StreamConfig()
        return _streaming.compute_factor_streamed(
            x, params, budget, key=key, eig_rtol=eig_rtol, config=cfg,
            gram_fn=gram_fn)

    tr = resolve_tracer(getattr(stream_config, "trace", None))
    with tr.span("h2d", "stage1_x"):
        x = jnp.asarray(x, dtype=jnp.float32)
    n = x.shape[0]
    with tr.span("stage1", "landmarks"):
        landmarks = select_landmarks(x, budget, key)
    with tr.span("stage1", "gram"):
        k_mm = gram_fn(landmarks, landmarks, params)
    with tr.span("stage1", "eig_projector"):
        # int(rank) waits for the eigh: the rank is a data-dependent shape.
        projector, evals, rank = _eig_projector(k_mm, params, eig_rtol)
        rank = int(rank)

    with tr.span("stage1", "project"):
        if compact:
            projector = projector[:, :rank]
        blocks = []
        for start in range(0, n, block_rows):
            xb = x[start:start + block_rows]
            blocks.append(jnp.dot(gram_fn(xb, landmarks, params), projector,
                                  precision=HIGHEST))
        G = jnp.concatenate(blocks, axis=0) if len(blocks) > 1 else blocks[0]

    return LowRankFactor(
        G=G, landmarks=landmarks, projector=projector, eigvals=evals,
        effective_rank=rank, kernel=params,
    )


def approximation_error(factor: LowRankFactor, x: jnp.ndarray,
                        params: KernelParams, probe: int = 256,
                        key: Optional[jax.Array] = None) -> float:
    """Relative Frobenius error of GG^T vs K on a random probe block (test aid)."""
    if key is None:
        key = jax.random.PRNGKey(1)
    n = x.shape[0]
    idx = np.asarray(jax.random.choice(key, n, shape=(min(probe, n),), replace=False))
    k_true = gram(x[idx], x[idx], params)
    g = factor.G[idx]
    k_hat = g @ g.T
    return float(jnp.linalg.norm(k_true - k_hat) / jnp.linalg.norm(k_true))
