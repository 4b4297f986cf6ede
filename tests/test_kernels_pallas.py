"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kernel_fn import KernelParams
from repro.kernels import ops, ref
from repro.kernels.smo import smo_epoch_pallas


@pytest.mark.parametrize("n,m,p", [
    (128, 128, 512),      # exactly one tile
    (130, 70, 33),        # everything ragged
    (17, 300, 1100),      # tall/skinny + multi-k
    (256, 128, 512),
])
@pytest.mark.parametrize("kind", ["rbf", "linear", "poly", "tanh"])
def test_gram_kernel_allclose(rng, n, m, p, kind):
    x = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(m, p)), jnp.float32)
    kp = KernelParams(kind, gamma=0.11, coef0=0.3, degree=2)
    got = ops.gram(x, z, kp, interpret=True)
    want = ref.gram_ref(x, z, kp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("tn,tm,tp", [(128, 128, 512), (8, 16, 32)])
def test_gram_kernel_tile_sweep(rng, tn, tm, tp):
    x = jnp.asarray(rng.normal(size=(40, 64)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(24, 64)), jnp.float32)
    kp = KernelParams("rbf", gamma=0.25)
    got = ops.gram(x, z, kp, tn=tn, tm=tm, tp=tp, interpret=True)
    want = ref.gram_ref(x, z, kp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n,m,p", [(64, 24, 32), (70, 9, 33), (33, 40, 100)])
@pytest.mark.parametrize("kind", ["rbf", "linear", "poly", "tanh"])
def test_gram_q8_fused_dequant_matches_ref(rng, n, m, p, kind):
    """The int8-wire gram kernel (fused in-register dequant) must agree with
    dequantise-then-gram to fp32 accumulation tolerance, ragged shapes
    included.  The symmetric codec keeps feature-axis zero padding exact for
    every kernel kind (RBF needs the true row norms)."""
    from repro.core.quant import quantize_rows
    x = rng.normal(size=(n, p)).astype(np.float32)
    z = jnp.asarray(rng.normal(size=(m, p)), jnp.float32)
    kp = KernelParams(kind, gamma=0.11, coef0=0.3, degree=2)
    v, s = quantize_rows(x, 32, symmetric=True)
    got = ops.gram_q8(jnp.asarray(v), jnp.asarray(s), z, kp, group=32,
                      tn=32, tm=8, tp=32, interpret=True)
    want = ref.gram_q8_ref(jnp.asarray(v), jnp.asarray(s), z, kp, group=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_gram_q8_rejects_affine_rbf_with_ragged_features(rng):
    """Affine zero-points would leak into the RBF row norms through the
    feature-axis padding — the wrapper rejects that combination when the
    scale table is concrete."""
    from repro.core.quant import quantize_rows
    x = (rng.normal(size=(32, 33)) + 5.0).astype(np.float32)
    z = jnp.asarray(rng.normal(size=(8, 33)), jnp.float32)
    v, s = quantize_rows(x, 32)                  # affine: nonzero zeros
    with pytest.raises(ValueError, match="symmetric"):
        ops.gram_q8(jnp.asarray(v), jnp.asarray(s), z,
                    KernelParams("rbf", gamma=0.1), group=32,
                    tn=32, tm=8, tp=32, interpret=True)
    # symmetric codec with the same shapes is fine
    vs, ss = quantize_rows(x, 32, symmetric=True)
    ops.gram_q8(jnp.asarray(vs), jnp.asarray(ss), z,
                KernelParams("rbf", gamma=0.1), group=32,
                tn=32, tm=8, tp=32, interpret=True)


def test_gram_q8_close_to_exact_gram(rng):
    """End-to-end codec error through the kernel stays at the scale/2 level:
    the quantised gram is a small perturbation of the exact one."""
    from repro.core.quant import quantize_rows
    x = rng.normal(size=(96, 48)).astype(np.float32)
    z = jnp.asarray(rng.normal(size=(32, 48)), jnp.float32)
    kp = KernelParams("rbf", gamma=0.1)
    v, s = quantize_rows(x, 32, symmetric=True)
    got = np.asarray(ops.gram_q8(jnp.asarray(v), jnp.asarray(s), z, kp,
                                 group=32, tn=32, tm=8, tp=16,
                                 interpret=True))
    exact = np.asarray(ref.gram_ref(jnp.asarray(x), z, kp))
    assert np.abs(got - exact).max() < 0.05
    assert np.abs(got - exact).mean() < 0.01


def _smo_inputs(rng, n=96, B=64, frac_pad=0.1):
    G = jnp.asarray(rng.normal(size=(n, B)) / np.sqrt(B), jnp.float32)
    y = jnp.asarray(rng.choice([-1.0, 1.0], size=n), jnp.float32)
    c = np.full((n,), 2.0, np.float32)
    c[int(n * (1 - frac_pad)):] = 0.0
    c = jnp.asarray(c)
    q = jnp.sum(G ** 2, axis=1)
    alpha = jnp.asarray(rng.uniform(0, 2, size=n).astype(np.float32)) * (c > 0)
    w = (alpha * y) @ G
    unch = jnp.asarray(rng.integers(0, 8, size=n), jnp.int32)
    return G, y, c, q, alpha, unch, w


@pytest.mark.parametrize("full_pass", [True, False])
@pytest.mark.parametrize("n,B", [(96, 64), (200, 96), (64, 128)])
def test_smo_epoch_allclose(rng, full_pass, n, B):
    G, y, c, q, alpha, unch, w = _smo_inputs(rng, n, B)
    a1, u1, w1, v1 = ops.smo_epoch(G, y, c, q, alpha, unch, w,
                                   full_pass=full_pass, interpret=True)
    a2, u2, w2, v2 = ref.smo_epoch_ref(
        G, y[:, None], c[:, None], q[:, None], alpha[:, None],
        unch[:, None], w[None, :], full_pass=full_pass)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2[:, 0]), atol=3e-6)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2[0]), atol=3e-5)
    assert np.mean(np.asarray(u1) == np.asarray(u2[:, 0])) > 0.98
    assert abs(float(v1) - float(v2[0, 0])) < 1e-4


def test_smo_epoch_monotone_dual(rng):
    """Coordinate ascent must not decrease the dual objective."""
    G, y, c, q, alpha, unch, w = _smo_inputs(rng, 128, 64, frac_pad=0.0)
    def dual(a, wv):
        return float(jnp.sum(a) - 0.5 * jnp.dot(wv, wv))
    d0 = dual(alpha, w)
    a, u, wv, _ = ops.smo_epoch(G, y, c, q, alpha, unch, w,
                                full_pass=True, interpret=True)
    d1 = dual(a, wv)
    a, u, wv, _ = ops.smo_epoch(G, y, c, q, a, u, wv,
                                full_pass=True, interpret=True)
    d2 = dual(a, wv)
    assert d1 >= d0 - 1e-4 and d2 >= d1 - 1e-4


def test_gram_accepts_bf16_inputs(rng):
    """Wrapper casts to f32 internally (SVM path is f32 by design)."""
    x = jnp.asarray(rng.normal(size=(40, 64)), jnp.bfloat16)
    z = jnp.asarray(rng.normal(size=(24, 64)), jnp.bfloat16)
    kp = KernelParams("rbf", gamma=0.25)
    got = ops.gram(x, z, kp, interpret=True)
    want = ref.gram_ref(x.astype(jnp.float32), z.astype(jnp.float32), kp)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("tn", [8, 64, 256])
def test_smo_epoch_tile_sweep(rng, tn):
    G, y, c, q, alpha, unch, w = _smo_inputs(rng, 96, 64)
    a1, u1, w1, v1 = ops.smo_epoch(G, y, c, q, alpha, unch, w,
                                   full_pass=True, tn=tn, interpret=True)
    a2, u2, w2, v2 = ref.smo_epoch_ref(
        G, y[:, None], c[:, None], q[:, None], alpha[:, None],
        unch[:, None], w[None, :], full_pass=True)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2[:, 0]), atol=3e-6)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2[0]), atol=3e-5)


def _smo_epoch_eager(G, y, c, q, alpha, unch, w, *, full_pass, tn=256):
    """The wrapper as it was before it became one program: eager casts,
    pads and reshapes around the jitted Pallas call, then eager slices."""
    n = G.shape[0]
    tn = min(tn, max(8, 1 << (n - 1).bit_length())) if n < tn else tn
    col = lambda v, dt: ops._pad_axis(jnp.asarray(v, dt).reshape(-1, 1), 0, tn)
    a, u, wv, viol = smo_epoch_pallas(
        ops._pad_axis(jnp.asarray(G, jnp.float32), 0, tn),
        col(y, jnp.float32), col(c, jnp.float32), col(q, jnp.float32),
        col(alpha, jnp.float32), col(unch, jnp.int32),
        jnp.asarray(w, jnp.float32).reshape(1, -1),
        full_pass=full_pass, tn=tn, interpret=True)
    return a[:n, 0], u[:n, 0], wv[0], viol[0, 0]


@pytest.mark.parametrize("full_pass", [True, False])
@pytest.mark.parametrize("n", [37, 200, 256, 300])
def test_smo_epoch_one_program_bit_identical(rng, n, full_pass):
    """One compiled program gives the eager composition's bits; 37, 200 and
    300 are not multiples of their tile, so the row padding is traced."""
    args = _smo_inputs(rng, n, 64)
    got = ops.smo_epoch(*args, full_pass=full_pass, interpret=True)
    want = _smo_epoch_eager(*args, full_pass=full_pass)
    for g, e in zip(got, want):
        assert g.shape == e.shape and g.dtype == e.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e))


class _Compiles(logging.Handler):
    """Collects the names of the programs XLA compiles under
    `jax.log_compiles()`."""

    def __init__(self):
        super().__init__()
        self.names = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Finished XLA compilation of "):
            self.names.append(msg.split(" of ", 1)[1].split(" in ")[0])


def test_smo_epoch_is_one_program_per_call(rng):
    # bench/metrics/smo_roofline.py sums the device time of the XLA modules
    # whose name holds its PROGRAM, "smo_epoch_pallas": the wrapper's one
    # module has to carry it, and no other module may.
    n, B = 45, 40          # a shape no other test compiles
    args = _smo_inputs(rng, n, B)
    text = ops.smo_epoch_pallas_flat.lower(
        *args, full_pass=True, shrink_k=5, tn=256, interpret=True).as_text()
    assert text.count("module @") == 1
    assert "module @jit_smo_epoch_pallas_flat " in text

    log, handler = logging.getLogger("jax"), _Compiles()
    log.addHandler(handler)
    try:
        with jax.log_compiles():
            out = ops.smo_epoch(*args, full_pass=True, interpret=True)
            jax.block_until_ready(out)
    finally:
        log.removeHandler(handler)
    assert handler.names == ["jit(smo_epoch_pallas_flat)"]

    size = ops.smo_epoch_pallas_flat._cache_size()
    out = ops.smo_epoch(*args, full_pass=True, interpret=True)
    jax.block_until_ready(out)
    assert ops.smo_epoch_pallas_flat._cache_size() == size
