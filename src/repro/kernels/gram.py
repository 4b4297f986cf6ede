"""Pallas TPU kernel: blocked batch kernel (gram) matrix computation.

Stage-1 hot spot of LPD-SVM ("batch kernel computation ... extremely efficient
on the GPU, using our own CUDA kernels").  TPU adaptation:

  * grid (n/tn, m/tm, p/tp); the contraction axis is the innermost grid
    dimension, so each (i, j) output tile accumulates partial X @ Z^T products
    in a float32 VMEM scratch across sequential k-steps (HBM->VMEM streaming of
    the p axis — the MXU sees hardware-aligned (tn, tp) x (tp, tm) tiles);
  * the squared row norms needed by the RBF epilogue are accumulated in VMEM
    alongside the dot products (one extra VPU rowsum per tile — negligible
    next to the MXU work), so the kernel makes a single pass over the inputs;
  * the kernel-function epilogue (exp / pow / tanh) is applied in-register on
    the final k-step before the tile is written back to HBM.

Block defaults are MXU-aligned: tn = tm = 128 lanes, tp = 512 floats.
VMEM footprint per step ~ (tn*tp + tm*tp + tn*tm) * 4B ~ 0.6 MB << 16 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.kernel_fn import HIGHEST, KernelParams


def _gram_kernel(x_ref, z_ref, o_ref, acc_ref, xsq_ref, zsq_ref, *,
                 params: KernelParams, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xsq_ref[...] = jnp.zeros_like(xsq_ref)
        zsq_ref[...] = jnp.zeros_like(zsq_ref)

    x = x_ref[...]  # (tn, tp)
    z = z_ref[...]  # (tm, tp)
    acc_ref[...] += jax.lax.dot_general(
        x, z, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)
    if params.kind == "rbf":  # norms only needed for the RBF epilogue
        xsq_ref[...] += jnp.sum(x * x, axis=1, keepdims=True)
        zsq_ref[...] += jnp.sum(z * z, axis=1, keepdims=True).T

    @pl.when(k == k_steps - 1)
    def _epilogue():
        dot = acc_ref[...]
        if params.kind == "linear":
            out = dot
        elif params.kind == "rbf":
            d2 = xsq_ref[...] + zsq_ref[...] - 2.0 * dot
            out = jnp.exp(-params.gamma * jnp.maximum(d2, 0.0))
        elif params.kind == "poly":
            out = (params.gamma * dot + params.coef0) ** params.degree
        elif params.kind == "tanh":
            out = jnp.tanh(params.gamma * dot + params.coef0)
        else:
            raise ValueError(params.kind)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("params", "tn", "tm", "tp", "interpret"))
def gram_pallas(x: jnp.ndarray, z: jnp.ndarray, params: KernelParams,
                *, tn: int = 128, tm: int = 128, tp: int = 512,
                interpret: bool = False) -> jnp.ndarray:
    """K[i, j] = k(x_i, z_j) for pre-padded inputs (shapes divisible by tiles).

    Use `repro.kernels.ops.gram` for the padding/dispatch wrapper.
    """
    n, p = x.shape
    m, _ = z.shape
    assert n % tn == 0 and m % tm == 0 and p % tp == 0, (n, m, p, tn, tm, tp)
    k_steps = p // tp
    grid = (n // tn, m // tm, k_steps)

    kernel = functools.partial(_gram_kernel, params=params, k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tn, tp), lambda i, j, k: (i, k)),
            pl.BlockSpec((tm, tp), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((tn, tm), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((tn, tm), jnp.float32),   # dot accumulator
            pltpu.VMEM((tn, 1), jnp.float32),    # ||x_i||^2
            pltpu.VMEM((1, tm), jnp.float32),    # ||z_j||^2
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, z)


# ---------------------------------------------------------------------------
# int8 wire variant: fused dequantisation of the streamed x operand
# ---------------------------------------------------------------------------

def _gram_kernel_q8(x_ref, sx_ref, zx_ref, z_ref, o_ref, acc_ref, xsq_ref,
                    zsq_ref, *, params: KernelParams, k_steps: int):
    """`_gram_kernel` with the x operand arriving as int8 wire data.

    The H2D copy moved one byte per element; the dequantisation
    x = q * scale + zero (per-row scale/zero from the host codec,
    `core/quant.py`) happens HERE, in VMEM registers on the (tn, tp) tile the
    MXU is about to consume — no fp32 copy of the chunk ever exists in HBM.
    The norms epilogue accumulates from the same dequantised registers, so
    one pass over the int8 input still produces exact-fp32-path semantics up
    to the codec's rounding.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xsq_ref[...] = jnp.zeros_like(xsq_ref)
        zsq_ref[...] = jnp.zeros_like(zsq_ref)

    # Fused dequant: int8 tile -> fp32 registers (sx/zx broadcast per row).
    x = x_ref[...].astype(jnp.float32) * sx_ref[...] + zx_ref[...]
    z = z_ref[...]  # (tm, tp), fp32 (landmarks stay device-resident)
    acc_ref[...] += jax.lax.dot_general(
        x, z, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)
    if params.kind == "rbf":
        xsq_ref[...] += jnp.sum(x * x, axis=1, keepdims=True)
        zsq_ref[...] += jnp.sum(z * z, axis=1, keepdims=True).T

    @pl.when(k == k_steps - 1)
    def _epilogue():
        dot = acc_ref[...]
        if params.kind == "linear":
            out = dot
        elif params.kind == "rbf":
            d2 = xsq_ref[...] + zsq_ref[...] - 2.0 * dot
            out = jnp.exp(-params.gamma * jnp.maximum(d2, 0.0))
        elif params.kind == "poly":
            out = (params.gamma * dot + params.coef0) ** params.degree
        elif params.kind == "tanh":
            out = jnp.tanh(params.gamma * dot + params.coef0)
        else:
            raise ValueError(params.kind)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("params", "tn", "tm", "tp", "interpret"))
def gram_pallas_q8(x_q8: jnp.ndarray, sx: jnp.ndarray, zx: jnp.ndarray,
                   z: jnp.ndarray, params: KernelParams,
                   *, tn: int = 128, tm: int = 128, tp: int = 512,
                   interpret: bool = False) -> jnp.ndarray:
    """K[i, j] = k(x_i, z_j) from a quantised x: int8 values (n, p) plus
    per-ROW fp32 scale/zero columns sx/zx of shape (n, 1).

    Pre-padded shapes (divisible by tiles), like `gram_pallas`.  Feature-axis
    zero padding of the int8 values is exact only when the padded rows carry
    zx = 0 (symmetric codec) — `repro.kernels.ops.gram_q8` checks that
    contract where the scale table is concrete (the streaming pipeline
    always quantises symmetrically).
    """
    n, p = x_q8.shape
    m, _ = z.shape
    assert n % tn == 0 and m % tm == 0 and p % tp == 0, (n, m, p, tn, tm, tp)
    assert sx.shape == (n, 1) and zx.shape == (n, 1), (sx.shape, zx.shape)
    k_steps = p // tp
    grid = (n // tn, m // tm, k_steps)

    kernel = functools.partial(_gram_kernel_q8, params=params, k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tn, tp), lambda i, j, k: (i, k)),   # int8 values
            pl.BlockSpec((tn, 1), lambda i, j, k: (i, 0)),    # row scales
            pl.BlockSpec((tn, 1), lambda i, j, k: (i, 0)),    # row zeros
            pl.BlockSpec((tm, tp), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((tn, tm), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((tn, tm), jnp.float32),   # dot accumulator
            pltpu.VMEM((tn, 1), jnp.float32),    # ||x_i||^2
            pltpu.VMEM((1, tm), jnp.float32),    # ||z_j||^2
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_q8, sx, zx, z)
