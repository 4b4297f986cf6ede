"""Pallas TPU kernel: causal flash attention (backbone hot spot).

The jnp two-level-chunked attention in `models/attention.py` is the
memory-correct formulation the dry-run lowers; THIS kernel is its TPU-native
form: one (bq, D) query tile stays resident while (bk, D) key/value tiles
stream HBM -> VMEM, with the online-softmax running max / normalizer / output
accumulator in VMEM scratch across the sequential kv grid dimension.

Layout: inputs are (BH, S, D) — batch x heads flattened into the first grid
axis (fully parallel), query blocks on the second (parallel), kv blocks on
the third (sequential/"arbitrary" so scratch carries state).  The causal mask
is computed from program ids; fully-masked kv tiles still execute (masked) —
the MXU cost of skipped tiles is the documented gap vs a production kernel
with block-sparse grid pruning.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
                  bq: int, bk: int, n_kv: int, scale: float, causal: bool):
    j = pl.program_id(2)
    i = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0]                                    # (bq, D)
    k = k_ref[0]                                    # (bk, D)
    v = v_ref[0]                                    # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(j == n_kv - 1)
    def _fini():
        o_ref[0] = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True, bq: int = 256,
                           bk: int = 256, interpret: bool = False):
    """q/k/v (BH, S, D), S divisible by bq and bk.  Returns (BH, S, D)."""
    BH, S, D = q.shape
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    n_q, n_kv = S // bq, S // bk
    scale = 1.0 / float(D) ** 0.5
    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, n_kv=n_kv,
                               scale=scale, causal=causal)
    return pl.pallas_call(
        kernel,
        grid=(BH, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, D), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, bk, D), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),       # running max
            pltpu.VMEM((bq, 1), jnp.float32),       # normalizer
            pltpu.VMEM((bq, D), jnp.float32),       # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
