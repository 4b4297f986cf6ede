"""Out-of-core stage 2: streamed row-block SMO must match `solve_batch`.

Pins down (a) streamed == monolithic (alpha, w, violation, epochs) including
shrinking, non-divisible tiles, and warm starts; (b) the full G is never
device-materialised under a small budget (transfer-guard + block-put spy);
(c) shrinking cuts per-epoch H2D bytes, not just FLOPs; (d) estimator / CV /
mesh entry points route onto the streamed solver.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.core.solver_stream as ss
from repro.core import (KernelParams, LPDSVM, StreamConfig, compute_factor,
                        cross_validate, solve_batch, solve_batch_streamed)
from repro.core.dual_solver import SolverConfig, TaskBatch
from repro.core.ovo import build_ovo_tasks
from repro.core.solver_stream import (auto_tile_rows, should_stream_stage2,
                                      stage2_block_bytes,
                                      stage2_monolithic_bytes,
                                      stage2_resident_bytes)
from repro.data import make_multiclass

KP = KernelParams("rbf", gamma=0.25)


def _problem(n=360, classes=3, budget=64, C=4.0, seed=9):
    x, y = make_multiclass(n, p=6, n_classes=classes, seed=seed)
    _, labels = np.unique(y, return_inverse=True)
    fac = compute_factor(jnp.asarray(x, jnp.float32), KP, budget)
    tasks, _ = build_ovo_tasks(labels, classes, C)
    return np.asarray(fac.G), tasks, labels


def _assert_matches(mono, res, rtol=1e-4, atol=1e-5):
    np.testing.assert_allclose(res.alpha, np.asarray(mono.alpha),
                               rtol=rtol, atol=atol)
    np.testing.assert_allclose(res.w, np.asarray(mono.w), rtol=rtol, atol=atol)
    np.testing.assert_allclose(res.violation, np.asarray(mono.violation),
                               rtol=1e-2, atol=1e-5)
    np.testing.assert_array_equal(res.epochs, np.asarray(mono.epochs))


@pytest.mark.parametrize("tile", [96, 67, 512])
def test_streamed_matches_monolithic(tile):
    """Divisible, ragged, and single-block tiles all reproduce the monolithic
    trajectory (global row order == sorted task idx order)."""
    G, tasks, _ = _problem()
    cfg = SolverConfig(tol=1e-2, max_epochs=300)
    mono = solve_batch(jnp.asarray(G), tasks, cfg)
    res = solve_batch_streamed(G, tasks, cfg,
                               stream_config=StreamConfig(tile_rows=tile))
    _assert_matches(mono, res)


def test_streamed_matches_with_disjoint_task_rows():
    """Regression: tasks living in disjoint G row ranges (CV folds do this)
    must keep cheap-epoch block skipping aligned with the COMPACTED row
    positions — a global-position slice silently starves late-range tasks."""
    rng = np.random.default_rng(11)
    n, rank = 400, 48
    G = rng.normal(size=(n, rank)).astype(np.float32) / np.sqrt(rank)
    n_pad = 104
    idx = np.zeros((2, n_pad), np.int32)
    idx[0, :100] = np.arange(100)            # task 0: rows 0..99
    idx[1, :100] = np.arange(300, 400)       # task 1: rows 300..399
    y = np.ones((2, n_pad), np.float32)
    y[:, 50:100] = -1.0
    c = np.zeros((2, n_pad), np.float32)
    c[:, :100] = 4.0
    tasks = TaskBatch(idx=jnp.asarray(idx), y=jnp.asarray(y),
                      c=jnp.asarray(c), alpha0=jnp.zeros((2, n_pad)))
    cfg = SolverConfig(tol=1e-4, max_epochs=300)
    mono = solve_batch(jnp.asarray(G), tasks, cfg)
    res = solve_batch_streamed(G, tasks, cfg,
                               stream_config=StreamConfig(tile_rows=64))
    _assert_matches(mono, res)


def test_streamed_matches_without_shrinking():
    G, tasks, _ = _problem(n=280)
    cfg = SolverConfig(tol=1e-2, max_epochs=200, shrink=False)
    mono = solve_batch(jnp.asarray(G), tasks, cfg)
    res = solve_batch_streamed(G, tasks, cfg,
                               stream_config=StreamConfig(tile_rows=80))
    _assert_matches(mono, res)


def test_warm_start_parity_and_speedup():
    """Warm-started solves (the C-grid pattern) match the monolithic path and
    converge in no more epochs than cold starts."""
    G, tasks, labels = _problem(C=1.0)
    cfg = SolverConfig(tol=1e-2, max_epochs=300)
    first = solve_batch(jnp.asarray(G), tasks, cfg)
    warm = [np.asarray(a) for a in np.asarray(first.alpha)]
    tasks4, _ = build_ovo_tasks(labels, 3, 4.0, alpha0=warm)
    mono = solve_batch(jnp.asarray(G), tasks4, cfg)
    res = solve_batch_streamed(G, tasks4, cfg,
                               stream_config=StreamConfig(tile_rows=96))
    _assert_matches(mono, res)
    cold4, _ = build_ovo_tasks(labels, 3, 4.0)
    cold = solve_batch_streamed(G, cold4, cfg,
                                stream_config=StreamConfig(tile_rows=96))
    assert res.epochs.sum() <= cold.epochs.sum()


def test_pallas_epoch_fn_streams():
    """The Pallas SMO kernel (interpret off-TPU) slots in as epoch_fn."""
    from repro.kernels.ops import smo_epoch
    G, tasks, _ = _problem(n=160, budget=48)
    cfg = SolverConfig(tol=1e-2, max_epochs=60)
    mono = solve_batch(jnp.asarray(G), tasks, cfg)
    res = solve_batch_streamed(G, tasks, cfg, epoch_fn=smo_epoch,
                               stream_config=StreamConfig(tile_rows=64))
    # Pallas pads/tiles differently from the jnp oracle: fp32 tolerance.
    np.testing.assert_allclose(res.w, np.asarray(mono.w), rtol=2e-3, atol=2e-3)


def test_full_G_never_device_materialized(monkeypatch):
    """Every H2D move is an explicit <= tile-row block put; a stray implicit
    transfer (the old solve_batch-on-host-G failure mode) raises under the
    guard, and the spy pins the largest block shape."""
    G, tasks, _ = _problem()
    cfg = SolverConfig(tol=1e-2, max_epochs=120)
    tile = 96
    puts = []
    orig = ss._put

    def spy(a, device=None):
        puts.append(np.shape(a))
        return orig(a, device)

    monkeypatch.setattr(ss, "_put", spy)
    guard = getattr(jax, "transfer_guard_host_to_device", None)
    cm = guard("disallow") if guard is not None else None
    if cm is None:
        pytest.skip("no transfer guard in this jax")
    with cm:
        solve_batch_streamed(G, tasks, cfg,
                             stream_config=StreamConfig(tile_rows=tile))
    two_d = [s for s in puts if len(s) == 2]
    assert two_d, "no G blocks streamed?"
    assert max(s[0] for s in two_d) == tile
    assert np.shape(G) not in two_d
    # sanity: the guard actually fires on the monolithic host-G path
    with guard("disallow"):
        with pytest.raises(Exception):
            solve_batch(G, tasks, SolverConfig(tol=1e-2, max_epochs=1))


def test_shrinking_cuts_h2d_bytes():
    """Bucket compaction streams only active-row blocks: cheap-epoch H2D
    bytes drop well below the full-pass bytes."""
    G, tasks, _ = _problem(n=480)
    cfg = SolverConfig(tol=1e-4, max_epochs=300)
    _, st = solve_batch_streamed(G, tasks, cfg, return_stats=True,
                                 stream_config=StreamConfig(tile_rows=96))
    assert st.full_passes >= 2 and len(st.active_history) >= 1
    assert min(st.epoch_bytes) < st.epoch_bytes[0] / 2
    cfg_off = SolverConfig(tol=1e-4, max_epochs=300, shrink=False)
    _, st_off = solve_batch_streamed(G, tasks, cfg_off, return_stats=True,
                                     stream_config=StreamConfig(tile_rows=96))
    per_epoch_on = st.rows_streamed / st.epochs
    per_epoch_off = st_off.rows_streamed / st_off.epochs
    assert per_epoch_on < per_epoch_off


# ------------------------------------------------------------ bf16 blocks

@pytest.mark.parametrize("dataset", ["checker", "spiral"])
def test_bf16_blocks_parity_tolerance(dataset):
    """`StreamConfig.block_dtype="bf16"` halves the streamed G bytes (the
    ROADMAP's bandwidth-doubling epilogue, measured on the wire) while the
    solution stays within tolerance of the fp32 monolithic solve on the
    classic RBF stress suites."""
    from repro.data import make_checker, make_two_spirals
    if dataset == "checker":
        x, y = make_checker(500, seed=3)
        kp = KernelParams("rbf", gamma=8.0)
    else:
        x, y = make_two_spirals(500, seed=4)
        kp = KernelParams("rbf", gamma=16.0)
    _, labels = np.unique(y, return_inverse=True)
    fac = compute_factor(jnp.asarray(x, jnp.float32), kp, 128)
    G = np.asarray(fac.G)
    n, rank = G.shape
    tasks, _ = build_ovo_tasks(labels, 2, 8.0)
    cfg = SolverConfig(tol=1e-2, max_epochs=300)
    mono = solve_batch(jnp.asarray(G), tasks, cfg)
    tile = 96
    _, s32 = solve_batch_streamed(
        G, tasks, cfg, return_stats=True,
        stream_config=StreamConfig(tile_rows=tile))
    res, sbf = solve_batch_streamed(
        G, tasks, cfg, return_stats=True,
        stream_config=StreamConfig(tile_rows=tile, block_dtype="bf16"))
    assert sbf.block_dtype == "bf16"
    # wire bytes: the G component of the first full pass halves exactly
    import math
    g32 = math.ceil(n / tile) * tile * rank * 4
    assert s32.epoch_bytes[0] - sbf.epoch_bytes[0] == g32 // 2
    # solution tolerance: weights, box feasibility, decisions, objective
    w_m = np.asarray(mono.w)
    assert np.max(np.abs(res.w - w_m)) <= 0.05 * np.max(np.abs(w_m))
    assert (res.alpha >= 0).all()
    assert (res.alpha <= np.asarray(tasks.c) + 1e-6).all()
    dec_m = G @ w_m.T
    dec_b = G @ res.w.T
    pred_m = (dec_m[:, 0] <= 0)
    pred_b = (dec_b[:, 0] <= 0)
    assert np.mean(pred_m != pred_b) <= 0.01
    err_m = np.mean(pred_m != (labels == 1))
    err_b = np.mean(pred_b != (labels == 1))
    assert abs(err_b - err_m) <= 0.02
    np.testing.assert_allclose(res.dual_obj, np.asarray(mono.dual_obj),
                               rtol=5e-3)
    # bf16 still converges below tol
    assert (res.violation < cfg.tol).all()


# ------------------------------------------------------------ int8 blocks

@pytest.mark.parametrize("dataset", ["checker", "spiral"])
def test_int8_blocks_parity_tolerance(dataset):
    """`StreamConfig.block_dtype="int8"` quarters the streamed G bytes
    (scales included — the exact byte model is asserted) while the solution
    stays within tolerance of the fp32 monolithic solve on the classic RBF
    stress suites: <= 1% decision flips, dual objective within rtol 5e-3,
    converged below the same tol."""
    import math
    from repro.core.quant import quant_scale_bytes
    from repro.core.solver_stream import wire_group
    from repro.data import make_checker, make_two_spirals
    if dataset == "checker":
        x, y = make_checker(500, seed=3)
        kp = KernelParams("rbf", gamma=8.0)
    else:
        x, y = make_two_spirals(500, seed=4)
        kp = KernelParams("rbf", gamma=16.0)
    _, labels = np.unique(y, return_inverse=True)
    fac = compute_factor(jnp.asarray(x, jnp.float32), kp, 128)
    G = np.asarray(fac.G)
    n, rank = G.shape
    tasks, _ = build_ovo_tasks(labels, 2, 8.0)
    cfg = SolverConfig(tol=1e-2, max_epochs=300)
    mono = solve_batch(jnp.asarray(G), tasks, cfg)
    tile = 96
    scfg8 = StreamConfig(tile_rows=tile, block_dtype="int8")
    _, s32 = solve_batch_streamed(
        G, tasks, cfg, return_stats=True,
        stream_config=StreamConfig(tile_rows=tile))
    res, s8 = solve_batch_streamed(G, tasks, cfg, return_stats=True,
                                   stream_config=scfg8)
    assert s8.block_dtype == "int8"
    # wire bytes: the G component of the first full pass quarters exactly,
    # scale-table bytes INCLUDED (the per-block (ng, 2) f32 tables)
    nb = math.ceil(n / tile)
    eff = wire_group(tile, scfg8)
    g32 = nb * tile * rank * 4
    g8 = nb * (tile * rank + quant_scale_bytes(tile, eff))
    assert s32.epoch_bytes[0] - s8.epoch_bytes[0] == g32 - g8
    assert g32 > 3 * g8                  # >= 3x with scales counted
    assert s8.bytes_scales > 0
    # solution tolerance: weights, box feasibility, decisions, objective
    w_m = np.asarray(mono.w)
    assert np.max(np.abs(res.w - w_m)) <= 0.1 * np.max(np.abs(w_m))
    assert (res.alpha >= 0).all()
    assert (res.alpha <= np.asarray(tasks.c) + 1e-6).all()
    pred_m = (G @ w_m.T)[:, 0] <= 0
    pred_8 = (G @ res.w.T)[:, 0] <= 0
    assert np.mean(pred_m != pred_8) <= 0.01
    err_m = np.mean(pred_m != (labels == 1))
    err_8 = np.mean(pred_8 != (labels == 1))
    assert abs(err_8 - err_m) <= 0.02
    np.testing.assert_allclose(res.dual_obj, np.asarray(mono.dual_obj),
                               rtol=5e-3)
    # int8 still converges below tol
    assert (res.violation < cfg.tol).all()


def test_int8_shrinking_consistency_and_byte_decay():
    """Shrinking through the int8 wire: compacted cheap epochs re-encode
    rows with their GLOBAL group scales, so the full-pass KKT check sees the
    same perturbed problem and converges in the monolithic epoch count —
    and the compaction still cuts per-epoch H2D bytes."""
    G, tasks, _ = _problem(n=480)
    cfg = SolverConfig(tol=1e-4, max_epochs=300)
    mono = solve_batch(jnp.asarray(G), tasks, cfg)
    res, st = solve_batch_streamed(
        G, tasks, cfg, return_stats=True,
        stream_config=StreamConfig(tile_rows=96, block_dtype="int8"))
    assert (res.violation < cfg.tol).all()
    # quantisation may cost a shrinking verification cycle (20-epoch
    # cadence) per task, but must not stall the full-pass KKT check — the
    # failure mode of re-grouped (inconsistent) compacted encodings is
    # epochs pinned at max_epochs
    assert res.epochs.max() < cfg.max_epochs
    assert res.epochs.sum() <= np.asarray(mono.epochs).sum() \
        + 20 * tasks.n_tasks + 8
    assert st.full_passes >= 2 and len(st.active_history) >= 1
    assert min(st.epoch_bytes) < st.epoch_bytes[0] / 2


def test_int8_warm_start_parity():
    """Warm starts (the C-grid pattern) flow through the quantised wire: the
    init pass accumulates w0 from dequantised blocks and converges in no
    more epochs than a cold int8 solve."""
    G, tasks, labels = _problem(C=1.0)
    cfg = SolverConfig(tol=1e-2, max_epochs=300)
    scfg = StreamConfig(tile_rows=96, block_dtype="int8")
    first = solve_batch_streamed(G, tasks, cfg, stream_config=scfg)
    warm = [np.asarray(a) for a in np.asarray(first.alpha)]
    tasks4, _ = build_ovo_tasks(labels, 3, 4.0, alpha0=warm)
    res = solve_batch_streamed(G, tasks4, cfg, stream_config=scfg)
    cold4, _ = build_ovo_tasks(labels, 3, 4.0)
    cold = solve_batch_streamed(G, cold4, cfg, stream_config=scfg)
    assert res.epochs.sum() <= cold.epochs.sum()
    assert (res.violation < cfg.tol).all()
    mono = solve_batch(jnp.asarray(G), tasks4, cfg)
    w_m = np.asarray(mono.w)
    assert np.max(np.abs(res.w - w_m)) <= 0.1 * np.max(np.abs(w_m))


def test_int8_wire_never_ships_f32_blocks(monkeypatch):
    """Every 2-D H2D block put on the int8 wire is int8 values or an (ng, 2)
    scale table — no fp32 G block ever crosses the bus."""
    G, tasks, _ = _problem()
    cfg = SolverConfig(tol=1e-2, max_epochs=60)
    puts = []
    orig = ss._put

    def spy(a, device=None):
        puts.append((np.shape(a), np.asarray(a).dtype))
        return orig(a, device)

    monkeypatch.setattr(ss, "_put", spy)
    solve_batch_streamed(G, tasks, cfg,
                         stream_config=StreamConfig(tile_rows=96,
                                                    block_dtype="int8"))
    two_d = [(s, dt) for s, dt in puts if len(s) == 2]
    assert two_d
    for shape, dt in two_d:
        assert dt == np.int8 or shape[1] == 2, (shape, dt)


# ------------------------------------------------------------- budget model

def test_stage2_memory_model_accounting():
    n, rank, T, n_pad = 10_000, 128, 3, 8_000
    assert stage2_resident_bytes(rank, T) == T * rank * 4
    assert stage2_block_bytes(100, rank, T) == 100 * (rank + 7 * T) * 4
    assert stage2_monolithic_bytes(n, rank, T, n_pad) == \
        (n * rank + T * (7 * n_pad + 2 * rank)) * 4
    small = auto_tile_rows(n, rank, T, StreamConfig(device_budget_bytes=1 << 20))
    large = auto_tile_rows(n, rank, T, StreamConfig(device_budget_bytes=1 << 28))
    assert small < large <= -(-n // 8) * 8
    assert auto_tile_rows(n, rank, T, StreamConfig(tile_rows=100)) == 104
    cfg = StreamConfig(device_budget_bytes=1 << 22)
    tile = auto_tile_rows(n, rank, T, cfg)
    if tile > cfg.min_chunk_rows:
        assert cfg.prefetch * stage2_block_bytes(tile, rank, T) \
            + stage2_resident_bytes(rank, T) <= cfg.device_budget_bytes
    assert should_stream_stage2(100_000, 512, 10, 80_000,
                                StreamConfig(device_budget_bytes=1 << 20))
    assert not should_stream_stage2(100, 16, 1, 100,
                                    StreamConfig(device_budget_bytes=1 << 30))


# ----------------------------------------------------------- entry points

def test_fit_streams_both_stages_under_budget():
    x, y = make_multiclass(500, p=6, n_classes=3, seed=2)
    plain = LPDSVM(KP, C=2.0, budget=96).fit(x, y)
    assert not plain.stats.stage2_streamed
    tiny = StreamConfig(device_budget_bytes=256 << 10)
    routed = LPDSVM(KP, C=2.0, budget=96, stream_config=tiny).fit(x, y)
    assert routed.stats.stage1_streamed and routed.stats.stage2_streamed
    assert routed.stats.stage2_stats is not None
    assert routed.stats.stage2_stats.rows_streamed > 0
    np.testing.assert_allclose(np.asarray(routed.W_), np.asarray(plain.W_),
                               rtol=1e-4, atol=1e-4)
    assert routed.score(x, y) == plain.score(x, y)
    np.testing.assert_array_equal(routed.predict_from_factor(),
                                  routed.predict(x))


def test_fit_respects_custom_solve_fn():
    calls = []

    def my_solve(G, tasks, config):
        calls.append(1)
        return solve_batch(jnp.asarray(np.asarray(G)), tasks, config)

    x, y = make_multiclass(200, p=4, n_classes=2, seed=3)
    svm = LPDSVM(KP, C=1.0, budget=48, solve_fn=my_solve,
                 stream_config=StreamConfig(device_budget_bytes=64 << 10))
    svm.fit(x, y)
    assert calls and not svm.stats.stage2_streamed


def test_cross_validate_routes_streamed():
    x, y = make_multiclass(400, p=5, n_classes=3, seed=4)
    err_plain, _ = cross_validate(x, y, KP, 2.0, budget=64, folds=3)
    tiny = StreamConfig(device_budget_bytes=128 << 10)
    err_stream, fac = cross_validate(x, y, KP, 2.0, budget=64, folds=3,
                                     stream_config=tiny)
    assert fac.streamed
    assert abs(err_plain - err_stream) < 1e-6


def test_polish_final_level_streams_int8():
    """`solve_polished` threads the quantised wire into its routed FINAL
    level: a forced-stream polish with `block_dtype="int8"` records int8
    stream stats and still matches the plain polished fit's predictions."""
    from repro.core import make_schedule, solve_polished
    x, y = make_multiclass(400, p=6, n_classes=3, seed=5)
    _, labels = np.unique(y, return_inverse=True)
    fac = compute_factor(jnp.asarray(x, jnp.float32), KP, 64)
    tasks, _ = build_ovo_tasks(labels, 3, 4.0)
    cfg = SolverConfig(tol=1e-2, max_epochs=300)
    sched = make_schedule(levels=2)
    res_plain = solve_polished(fac, tasks, cfg, sched)
    fac_host = type(fac)(G=np.asarray(fac.G), landmarks=fac.landmarks,
                         projector=fac.projector, eigvals=fac.eigvals,
                         effective_rank=fac.effective_rank, kernel=fac.kernel,
                         streamed=True)
    res8, trace = solve_polished(
        fac_host, tasks, cfg, sched, stream=True,
        stream_config=StreamConfig(tile_rows=96, block_dtype="int8"),
        return_trace=True)
    assert trace.final.streamed
    assert trace.final.stream_stats.block_dtype == "int8"
    assert trace.final.stream_stats.bytes_scales > 0
    G = np.asarray(fac.G)
    from repro.core.ovo import class_pairs, ovo_vote
    pairs = class_pairs(3)
    v_plain = ovo_vote(G @ np.asarray(res_plain.w).T, pairs, 3)
    v8 = ovo_vote(G @ np.asarray(res8.w).T, pairs, 3)
    assert np.mean(v_plain == v8) >= 0.99


def test_streamed_mesh_single_device_matches():
    from repro.core import solve_tasks_streamed_mesh
    G, tasks, _ = _problem(n=240, budget=48)
    cfg = SolverConfig(tol=1e-2, max_epochs=120)
    mesh = jax.make_mesh((1,), ("data",))
    res = solve_tasks_streamed_mesh(mesh, G, tasks, cfg,
                                    stream_config=StreamConfig(tile_rows=64))
    mono = solve_batch(jnp.asarray(G), tasks, cfg)
    _assert_matches(mono, res)
