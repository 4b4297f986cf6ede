"""Out-of-core stage 1: the chunked pipeline must be invisible numerically.

Pins down (a) chunked == monolithic G for awkward shapes, (b) the memory
budget model routes `compute_factor` / `LPDSVM.fit` onto the chunked path,
(c) the Pallas gram kernel slots into the streaming loop, and (d) disjoint
chunk streams over several devices still produce the same factor.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (KernelParams, LPDSVM, StreamConfig, auto_chunk_rows,
                        compute_factor, compute_factor_streamed, should_stream,
                        stream_factor_rows)
from repro.core.streaming import chunk_bytes, monolithic_bytes, resident_bytes

KP = KernelParams("rbf", gamma=0.5)


def _data(n, p=9, seed=0):
    return np.random.default_rng(seed).normal(size=(n, p)).astype(np.float32)


@pytest.mark.parametrize("n,budget,chunk", [
    (256, 64, 64),      # divisible
    (257, 64, 64),      # one straggler row
    (300, 48, 77),      # nothing divides anything
    (100, 32, 512),     # single chunk covers everything
    (200, 200, 33),     # budget >= n: landmarks are all of x
])
def test_chunked_matches_monolithic(n, budget, chunk):
    x = _data(n)
    mono = compute_factor(x, KP, budget)
    cfg = StreamConfig(chunk_rows=chunk)
    stre = compute_factor(x, KP, budget, stream=True, stream_config=cfg)
    assert stre.streamed and not mono.streamed
    assert isinstance(stre.G, np.ndarray)          # host-resident buffer
    assert stre.effective_rank == mono.effective_rank
    np.testing.assert_allclose(stre.G, np.asarray(mono.G),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("prefetch", [1, 2, 4])
def test_prefetch_depth_does_not_change_results(prefetch):
    x = _data(310)
    fac = compute_factor(x, KP, 64)
    out = stream_factor_rows(x, fac.landmarks, fac.projector, KP,
                             chunk_rows=49, prefetch=prefetch)
    np.testing.assert_allclose(out, np.asarray(fac.G), rtol=1e-5, atol=1e-5)


def test_preallocated_out_buffer_is_filled_in_place():
    x = _data(128)
    fac = compute_factor(x, KP, 32)
    out = np.full((128, fac.projector.shape[1]), np.nan, np.float32)
    ret = stream_factor_rows(x, fac.landmarks, fac.projector, KP,
                             chunk_rows=50, out=out)
    assert ret is out and np.isfinite(out).all()


def test_pallas_gram_fn_streams():
    from repro.kernels.ops import gram as gram_pallas
    x = _data(140, p=5)
    mono = compute_factor(x, KP, 48)
    stre = compute_factor_streamed(x, KP, 48, gram_fn=gram_pallas,
                                   config=StreamConfig(chunk_rows=33))
    # Pallas pads/tiles differently from the jnp reference: fp32 tolerance.
    np.testing.assert_allclose(stre.G, np.asarray(mono.G),
                               rtol=2e-4, atol=2e-4)


# ----------------------------------------------------------- int8 wire chunks

def test_int8_chunks_quarter_bytes_and_stay_close():
    """`StreamConfig.stage1_dtype="int8"`: chunk H2D bytes quarter (scales
    included, exact byte model) and the factor stays within the codec's
    error bound of the f32 streamed factor."""
    from repro.core.quant import quant_bytes
    x = _data(700, p=9)
    cfg32 = StreamConfig(chunk_rows=128)
    cfg8 = StreamConfig(chunk_rows=128, stage1_dtype="int8")
    s32 = compute_factor_streamed(x, KP, 64, config=cfg32)
    s8 = compute_factor_streamed(x, KP, 64, config=cfg8)
    st32, st8 = s32.stage1_stats, s8.stage1_stats
    assert st32.wire_dtype == "f32" and st8.wire_dtype == "int8"
    assert st32.bytes_h2d == 700 * 9 * 4
    expected = sum(quant_bytes(min(128, 700 - s), 9, cfg8.quant_group_rows)
                   for s in range(0, 700, 128))
    assert st8.bytes_h2d == expected
    assert st8.bytes_scales > 0
    assert st32.bytes_h2d > 3 * st8.bytes_h2d          # >= 3x incl. scales
    # parity: the kernel epilogue contracts the quantisation noise; the
    # factor stays close to the exact streamed one
    assert np.abs(s8.G - s32.G).max() < 0.05
    assert np.abs(s8.G - s32.G).mean() < 0.005
    assert s8.effective_rank == s32.effective_rank


def test_int8_chunks_through_fit():
    """End-to-end: an LPDSVM fit with a quantised stage-1 wire classifies
    like the f32 fit (both stages streamed)."""
    x = _data(600, p=6, seed=1)
    y = (x[:, 0] * x[:, 1] > 0).astype(int)
    kp = KernelParams("rbf", gamma=1.0)
    plain = LPDSVM(kp, C=2.0, budget=96).fit(x, y)
    cfg = StreamConfig(device_budget_bytes=256 << 10, stage1_dtype="int8",
                       block_dtype="int8")
    svm = LPDSVM(kp, C=2.0, budget=96, stream_config=cfg).fit(x, y)
    assert svm.stats.stage1_streamed and svm.stats.stage2_streamed
    assert svm.stats.stage1_stats is not None
    assert svm.stats.stage1_stats.wire_dtype == "int8"
    assert svm.stats.stage2_stats.block_dtype == "int8"
    assert abs(svm.score(x, y) - plain.score(x, y)) <= 0.02


def test_int8_gram_q8_fn_injectable():
    """The Pallas fused-dequant kernel slots in as gram_q8_fn (interpret
    off-TPU), matching the jnp dequant oracle path."""
    from repro.core.streaming import stream_factor_blocks
    from repro.kernels import ops
    x = _data(140, p=5)
    fac = compute_factor(x, KP, 48)

    def pallas_q8(v, s, z, params, group):
        return ops.gram_q8(v, s, z, params, group=group, tn=32, tm=16, tp=8,
                           interpret=True)

    blocks = (x[s:s + 33] for s in range(0, 140, 33))
    out = stream_factor_blocks(
        blocks, 140, fac.landmarks, fac.projector, KP, wire_dtype="int8",
        gram_q8_fn=pallas_q8)
    oracle = stream_factor_rows(x, fac.landmarks, fac.projector, KP,
                                chunk_rows=33, wire_dtype="int8")
    np.testing.assert_allclose(out, oracle, rtol=2e-4, atol=2e-4)


# -------------------------------------------------------- stage-1 autotune

def test_stage1_autotune_plumbing(monkeypatch):
    """`tune_prefetch` is applied ONCE, after the first full pipeline
    window, and the tuned depth surfaces in the stats (ROADMAP stage-1
    overlap item)."""
    import repro.core.streaming as streaming
    calls = []

    def fake_tune(put, drain, prefetch, cap):
        calls.append((prefetch, cap))
        return 5

    monkeypatch.setattr(streaming, "tune_prefetch", fake_tune)
    x = _data(900)
    fac = compute_factor(x, KP, 32)
    from repro.core.streaming import Stage1StreamStats, stream_factor_rows
    st = Stage1StreamStats()
    out = stream_factor_rows(x, fac.landmarks, fac.projector, KP,
                             chunk_rows=64, prefetch=2,
                             autotune_prefetch=True, prefetch_cap=6,
                             stats=st)
    assert calls == [(2, 6)]
    assert st.prefetch_final == 5
    np.testing.assert_allclose(out, np.asarray(fac.G), rtol=1e-5, atol=1e-5)
    # disabled: depth untouched
    calls.clear()
    st2 = Stage1StreamStats()
    stream_factor_rows(x, fac.landmarks, fac.projector, KP,
                       chunk_rows=64, prefetch=3, stats=st2)
    assert not calls and st2.prefetch_final == 3


def test_stage1_autotune_routed_from_config():
    """`compute_factor_streamed` threads the config's autotune knobs through
    and records the chunk traffic on the factor."""
    x = _data(800)
    cfg = StreamConfig(chunk_rows=64, autotune_prefetch=True, prefetch_cap=4)
    fac = compute_factor_streamed(x, KP, 48, config=cfg)
    st = fac.stage1_stats
    assert st is not None and st.chunks == -(-800 // 64)
    assert st.rows == 800
    assert 2 <= st.prefetch_final <= 4     # tuned within [prefetch, cap]
    off = StreamConfig(chunk_rows=64, autotune_prefetch=False)
    st_off = compute_factor_streamed(x, KP, 48, config=off).stage1_stats
    assert st_off.prefetch_final == off.prefetch


# ------------------------------------------------------------- budget model

def test_memory_model_accounting():
    n, p, B = 10_000, 64, 512
    assert monolithic_bytes(n, p, B) == \
        (n * p + 2 * n * B) * 4 + resident_bytes(p, B)
    assert chunk_bytes(100, p, B) == 100 * (p + 2 * B) * 4
    # bigger budget -> bigger auto chunks, clamped to n
    small = auto_chunk_rows(n, p, B, StreamConfig(device_budget_bytes=8 << 20))
    large = auto_chunk_rows(n, p, B, StreamConfig(device_budget_bytes=1 << 30))
    assert small < large <= n
    # the chosen chunk respects the budget (above the min-chunk floor)
    cfg = StreamConfig(device_budget_bytes=64 << 20)
    r = auto_chunk_rows(n, p, B, cfg)
    if r > cfg.min_chunk_rows:
        assert cfg.prefetch * chunk_bytes(r, p, B) + resident_bytes(p, B) \
            <= cfg.device_budget_bytes


def test_should_stream_thresholds():
    cfg = StreamConfig(device_budget_bytes=1 << 20)
    assert should_stream(100_000, 32, 512, cfg)
    assert not should_stream(100, 8, 32, StreamConfig(device_budget_bytes=1 << 30))


def test_fit_routes_through_streaming_when_budget_forces_it():
    x = _data(600, p=6, seed=1)
    y = (x[:, 0] * x[:, 1] > 0).astype(int)
    kp = KernelParams("rbf", gamma=1.0)
    plain = LPDSVM(kp, C=2.0, budget=96).fit(x, y)
    assert not plain.stats.stage1_streamed
    # 256 KiB budget: monolithic (600 x 96) working set cannot fit
    tiny = StreamConfig(device_budget_bytes=256 << 10)
    routed = LPDSVM(kp, C=2.0, budget=96, stream_config=tiny).fit(x, y)
    assert routed.stats.stage1_streamed and routed.factor.streamed
    np.testing.assert_allclose(np.asarray(routed.W_), np.asarray(plain.W_),
                               rtol=1e-4, atol=1e-4)
    assert routed.score(x, y) == plain.score(x, y)


def test_fit_stays_monolithic_under_roomy_budget():
    x = _data(200, p=4, seed=2)
    y = (x[:, 0] > 0).astype(int)
    roomy = StreamConfig(device_budget_bytes=1 << 30)
    svm = LPDSVM(KernelParams("rbf", gamma=1.0), C=1.0, budget=64,
                 stream_config=roomy).fit(x, y)
    assert not svm.stats.stage1_streamed


# --------------------------------------------------------------- multi-device

def test_disjoint_chunk_streams_over_devices():
    """4 fake CPU devices, each owning a disjoint chunk stream (subprocess:
    XLA device-count flags must precede jax import)."""
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = src
    code = r"""
import jax, numpy as np
from repro.core import KernelParams, compute_factor
from repro.core.distributed import compute_factor_streamed_mesh, stream_factor_over_mesh
from repro.core.streaming import StreamConfig

assert len(jax.devices()) == 4
kp = KernelParams("rbf", gamma=0.5)
x = np.random.default_rng(0).normal(size=(403, 7)).astype(np.float32)
mono = compute_factor(x, kp, 64)
mesh = jax.make_mesh((2, 2), ("data", "model"))
out = stream_factor_over_mesh(mesh, x, mono.landmarks, mono.projector, kp,
                              chunk_rows=37)
np.testing.assert_allclose(out, np.asarray(mono.G), rtol=1e-5, atol=1e-5)
fac = compute_factor_streamed_mesh(mesh, x, kp, 64,
                                   stream_config=StreamConfig(chunk_rows=50))
assert fac.streamed
np.testing.assert_allclose(fac.G, np.asarray(mono.G), rtol=1e-5, atol=1e-5)
print("MESH-STREAM-OK")
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH-STREAM-OK" in out.stdout
