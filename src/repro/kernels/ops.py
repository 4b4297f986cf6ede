"""Public wrappers around the Pallas kernels: padding, tiling, dispatch.

On the CPU the kernels execute with interpret=True (Python-level
execution of the kernel body); on TPU they compile to Mosaic.  The wrappers
make either path a drop-in replacement for the pure-jnp reference functions
(`core.kernel_fn.gram`, `core.dual_solver.epoch_ref`).

`smo_epoch` is one compiled program, `smo_epoch_pallas_flat`: the streamed
stage 2 calls it once per (task, block) window, so its casts, pads,
reshapes and slices run inside the program instead of as about ten eager
dispatches around the kernel.  The program's XLA module is named
`jit_smo_epoch_pallas_flat`; `bench/metrics/smo_roofline.py` finds the
kernel's device time by the substring `smo_epoch_pallas`, so keep it in
the name.  `gram`, `gram_q8` and `flash_attention` pad and slice eagerly
around their jitted kernels (`gram_q8` checks concrete scales on the host).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kernel_fn import KernelParams
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gram import gram_pallas, gram_pallas_q8
from repro.kernels.smo import smo_epoch_pallas


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_axis(a: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    size = a.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def gram(x: jnp.ndarray, z: jnp.ndarray, params: KernelParams, *,
         tn: int = 128, tm: int = 128, tp: int = 512,
         interpret: Optional[bool] = None) -> jnp.ndarray:
    """Batch kernel matrix K(x, z) via the Pallas kernel, any shapes.

    Zero-padding the feature axis is exact for every supported kernel (it adds
    zero to the dot products and squared norms); padded rows/cols are sliced
    off the output.
    """
    if interpret is None:
        interpret = _default_interpret()
    n, m = x.shape[0], z.shape[0]
    x = _pad_axis(_pad_axis(jnp.asarray(x, jnp.float32), 1, tp), 0, tn)
    z = _pad_axis(_pad_axis(jnp.asarray(z, jnp.float32), 1, tp), 0, tm)
    out = gram_pallas(x, z, params, tn=tn, tm=tm, tp=tp, interpret=interpret)
    return out[:n, :m]


def gram_q8(values: jnp.ndarray, scales: jnp.ndarray, z: jnp.ndarray,
            params: KernelParams, *, group: int = 32,
            tn: int = 128, tm: int = 128, tp: int = 512,
            interpret: Optional[bool] = None) -> jnp.ndarray:
    """Batch kernel matrix from a quantised x operand, any shapes.

    ``values`` is the (n, p) int8 wire block and ``scales`` the compact
    (ng, 2) per-row-group scale/zero table (`core/quant.py`); z stays fp32
    (device-resident landmarks).  The compact table is expanded to per-row
    (n, 1) scale/zero columns on device — 8 bytes per GROUP cross the bus,
    not 8 per row — and dequantisation is fused into the Pallas kernel's
    tile loads (`gram_pallas_q8`), so no fp32 copy of x is ever
    materialised in HBM.

    Padding contract: padded ROWS get scale 0 / zero 0 (dequantise to exact
    zeros, sliced off the output anyway).  Feature-axis zero padding of the
    int8 values dequantises to the row's zero-point, which cancels in the
    dot (z's padded columns are fp32 zeros) but NOT in the RBF row norms —
    so RBF with a ragged feature axis requires the symmetric codec
    (zero = 0), which is what the stage-1 streaming pipeline emits.  The
    contract is checked here when the scale table is concrete; under jit
    (traced scales) the caller must guarantee it.
    """
    if interpret is None:
        interpret = _default_interpret()
    n, p = values.shape
    m = z.shape[0]
    if params.kind == "rbf" and p % tp:
        try:
            zero_points = np.asarray(scales)[:, 1]
        except Exception:        # traced under jit: contract is the caller's
            zero_points = None
        if zero_points is not None and np.any(zero_points != 0.0):
            raise ValueError(
                "gram_q8: RBF with a feature axis padded to the tile "
                f"(p={p}, tp={tp}) requires the symmetric codec — affine "
                "zero-points would leak into the row norms; quantise with "
                "quantize_rows(..., symmetric=True)")
    ng = scales.shape[0]
    sx = jnp.repeat(scales[:, 0], group, total_repeat_length=ng * group)[:n]
    zx = jnp.repeat(scales[:, 1], group, total_repeat_length=ng * group)[:n]
    vq = _pad_axis(_pad_axis(jnp.asarray(values, jnp.int8), 1, tp), 0, tn)
    sx = _pad_axis(sx.reshape(-1, 1).astype(jnp.float32), 0, tn)
    zx = _pad_axis(zx.reshape(-1, 1).astype(jnp.float32), 0, tn)
    zp = _pad_axis(_pad_axis(jnp.asarray(z, jnp.float32), 1, tp), 0, tm)
    out = gram_pallas_q8(vq, sx, zx, zp, params, tn=tn, tm=tm, tp=tp,
                         interpret=interpret)
    return out[:n, :m]


def flash_attention(q, k, v, *, causal: bool = True, bq: int = 256,
                    bk: int = 256, interpret: Optional[bool] = None):
    """Causal flash attention over (B, H, S, D) tensors (pads S to blocks).

    On TPU this is the Mosaic kernel; off-TPU it interprets.  The jnp
    two-level-chunked path in models/attention.py remains the default for
    dry-run lowering; this entry point is for TPU deployment + validation.
    """
    if interpret is None:
        interpret = _default_interpret()
    B, H, S, D = q.shape
    bq = min(bq, S)
    bk = min(bk, S)
    pad = (-S) % max(bq, bk)
    flat = lambda a: _pad_axis(a.reshape(B * H, S, D), 1, max(bq, bk))
    qf, kf, vf = flat(q), flat(k), flat(v)
    if pad:  # padded kv rows must never win the softmax: mask via causal rows
        assert causal, "padding currently supported for causal attention only"
    out = flash_attention_pallas(qf, kf, vf, causal=causal, bq=bq, bk=bk,
                                 interpret=interpret)
    return out[:, :S].reshape(B, H, S, D)


def smo_epoch(G, y, c, q, alpha, unchanged, w, *, full_pass: bool,
              shrink_k: int = 5, tn: int = 256,
              interpret: Optional[bool] = None):
    """One shrinking-aware coordinate-ascent epoch (flat 1-D vectors in/out).

    Row padding uses c = 0, which the kernel treats as inert, so results are
    exact for any n.  Returns (alpha, unchanged, w, viol_scalar).  One
    dispatch of one compiled program (`smo_epoch_pallas_flat`) per call.
    """
    if interpret is None:
        interpret = _default_interpret()
    return smo_epoch_pallas_flat(G, y, c, q, alpha, unchanged, w,
                                 full_pass=full_pass, shrink_k=shrink_k,
                                 tn=tn, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("full_pass", "shrink_k", "tn", "interpret"))
def smo_epoch_pallas_flat(G, y, c, q, alpha, unchanged, w, *,
                          full_pass: bool, shrink_k: int, tn: int,
                          interpret: bool):
    """`smo_epoch`'s casts, row padding, reshapes, Pallas call and slices as
    one program.  Keep `smo_epoch_pallas` in the name: the module name
    carries it to the benchmark's kernel-time reader (module docstring)."""
    n = G.shape[0]
    tn = min(tn, max(8, 1 << (n - 1).bit_length())) if n < tn else tn
    Gp = _pad_axis(jnp.asarray(G, jnp.float32), 0, tn)
    pad1 = lambda v, dt: _pad_axis(jnp.asarray(v, dt).reshape(-1, 1), 0, tn)
    a, u, wv, viol = smo_epoch_pallas(
        Gp, pad1(y, jnp.float32), pad1(c, jnp.float32), pad1(q, jnp.float32),
        pad1(alpha, jnp.float32), pad1(unchanged, jnp.int32),
        jnp.asarray(w, jnp.float32).reshape(1, -1),
        full_pass=full_pass, shrink_k=shrink_k, tn=tn, interpret=interpret)
    return a[:n, 0], u[:n, 0], wv[0], viol[0, 0]
