"""Pallas TPU kernel: one SMO (dual coordinate ascent) epoch.

Stage-2 hot spot.  The paper's GPU design keeps the weight vector w in the
fast scratchpad memory of a SINGLE streaming multiprocessor, because "the SMO
loop is memory-bound, not compute-bound (it is dominated by computing inner
products of vectors of dimension B)" and cross-SM communication would kill the
multi-million-steps-per-second loop.  TPU adaptation of the same insight:

  * w (1, B) lives in a VMEM scratch buffer that persists across the
    sequential grid — the TPU analogue of the SM scratchpad;
  * G is streamed HBM -> VMEM one (tn, B) row tile per grid step; every row is
    visited once per epoch (round-robin order, as in the paper);
  * the truncated-Newton coordinate update runs in a lax.fori_loop INSIDE the
    kernel: dot(w, g_i) is a VPU reduction over B lanes; there is no MXU work,
    which is exactly why this kernel's roofline is memory-bound (see
    EXPERIMENTS.md §Roofline, SVM rows);
  * shrinking is carried in an int32 "unchanged-touch counter" per variable;
    full passes (every 20th epoch, the paper's eta ~ 5% re-check budget) are a
    separate compile of the same kernel with full_pass=True.

The epoch-level bucket compaction that turns shrinking into actual time
savings (paper: "the memory demand for the relevant sub-matrix of G reduces")
lives in `repro/core/compact.py` — it shrinks n_pad between epochs, which
shrinks this kernel's HBM traffic proportionally.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

Q_FLOOR = 1e-12


def _smo_kernel(g_ref, y_ref, c_ref, q_ref, alpha_ref, unch_ref, w_ref,
                alpha_out, unch_out, w_out, viol_out,
                w_s, viol_s, *, tn: int, n_blocks: int,
                full_pass: bool, shrink_k: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        w_s[...] = w_ref[...]
        viol_s[...] = jnp.zeros_like(viol_s)

    # copy this tile's alpha / counters into the output block, update in place
    alpha_out[...] = alpha_ref[...]
    unch_out[...] = unch_ref[...]

    def body(r, viol):
        row = g_ref[pl.ds(r, 1), :]                    # (1, B)
        a = alpha_out[pl.ds(r, 1), :]                  # (1, 1)
        y = y_ref[pl.ds(r, 1), :]
        c = c_ref[pl.ds(r, 1), :]
        q = q_ref[pl.ds(r, 1), :]
        u = unch_out[pl.ds(r, 1), :]

        w = w_s[...]                                   # (1, B)
        margin = jnp.sum(w * row, axis=1, keepdims=True)   # (1, 1) VPU reduce
        g = 1.0 - y * margin
        real = c > 0.0
        if full_pass:
            active = real
        else:
            active = jnp.logical_and(real, u < shrink_k)

        at_lo = a <= 0.0
        at_hi = a >= c
        pg = jnp.where(at_lo, jnp.maximum(g, 0.0),
                       jnp.where(at_hi, jnp.minimum(g, 0.0), g))
        a_new = jnp.clip(a + g / jnp.maximum(q, Q_FLOOR), 0.0, c)
        a_new = jnp.where(active, a_new, a)
        delta = a_new - a

        w_s[...] = w + (delta * y) * row               # rank-1 w update
        alpha_out[pl.ds(r, 1), :] = a_new
        changed = jnp.abs(delta) > 0.0
        u_new = jnp.where(changed, 0, u + 1)
        unch_out[pl.ds(r, 1), :] = jnp.where(active, u_new, u)
        return jnp.maximum(viol, jnp.where(active, jnp.abs(pg), 0.0))

    # The running violation stays a (1, 1) vector: Mosaic has no scalar
    # loads or stores on VMEM refs.
    viol_s[...] = jax.lax.fori_loop(0, tn, body, viol_s[...])

    @pl.when(i == n_blocks - 1)
    def _fini():
        w_out[...] = w_s[...]
        viol_out[...] = viol_s[...]


@functools.partial(
    jax.jit,
    static_argnames=("full_pass", "shrink_k", "tn", "interpret"))
def smo_epoch_pallas(G, y, c, q, alpha, unchanged, w, *,
                     full_pass: bool, shrink_k: int = 5, tn: int = 256,
                     interpret: bool = False):
    """One epoch over pre-padded (n_pad % tn == 0) per-task data.

    Shapes: G (n, B); y/c/q/alpha (n, 1) f32; unchanged (n, 1) i32; w (1, B).
    Returns (alpha, unchanged, w, viol[1,1]).
    """
    n, B = G.shape
    assert n % tn == 0, (n, tn)
    n_blocks = n // tn
    kernel = functools.partial(_smo_kernel, tn=tn, n_blocks=n_blocks,
                               full_pass=full_pass, shrink_k=shrink_k)
    col = lambda i: (i, 0)
    rep = lambda i: (0, 0)
    return pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((tn, B), col),      # G tile
            pl.BlockSpec((tn, 1), col),      # y
            pl.BlockSpec((tn, 1), col),      # c
            pl.BlockSpec((tn, 1), col),      # q
            pl.BlockSpec((tn, 1), col),      # alpha
            pl.BlockSpec((tn, 1), col),      # unchanged
            pl.BlockSpec((1, B), rep),       # w (read once)
        ],
        out_specs=[
            pl.BlockSpec((tn, 1), col),
            pl.BlockSpec((tn, 1), col),
            pl.BlockSpec((1, B), rep),
            pl.BlockSpec((1, 1), rep),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, B), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, B), jnp.float32),   # w scratchpad (the SM trick)
            pltpu.VMEM((1, 1), jnp.float32),   # running max violation
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(G, y, c, q, alpha, unchanged, w)
