"""Cross-validation, grid search, and warm starts (paper sec. 4 + Table 3).

The paper's point: parameter tuning is where the two-stage design pays off —
  * the factor G depends only on the kernel (gamma), NOT on C or the fold
    split, so one stage-1 run serves folds x C-grid x OVO-pairs solves;
  * "we simply fix the feature space representation once for the whole data
    set, pre-compute G, and only then sub-divide the data into folds";
  * "when searching a grid of growing values of C, we warm-start the solver
    from the optimal solution of the nearest value of C already completed".

All (pair x fold) tasks for one (gamma, C) cell are solved as ONE TaskBatch,
which is also what the sharded task farm consumes — the paper's "11,250 binary
SVMs ... far more parallelism than we need".
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dual_solver import SolverConfig, TaskBatch, solve_batch
from repro.core.kernel_fn import KernelParams, gram
from repro.core.nystrom import LowRankFactor, compute_factor, wait_for_factor
from repro.core.ovo import build_ovo_tasks, class_pairs, ovo_vote
from repro.core.polish import PolishSchedule, make_schedule, solve_polished
from repro.core.solver_stream import route_stage2, solve_streamed_auto
from repro.core.streaming import StreamConfig
from repro.core.trace import resolve as resolve_tracer


def _solve_routed(factor: LowRankFactor, tasks: TaskBatch,
                  config: SolverConfig, solve_fn: Callable,
                  stream, stream_config: Optional[StreamConfig],
                  polish_schedule: Optional[PolishSchedule] = None):
    """Stage-2 dispatch (see `solver_stream.route_stage2`, shared with
    `LPDSVM._solve_stage2`); with a `polish_schedule` the cell runs the
    coarse-to-fine ladder (`core/polish.py`), composing with the C-grid warm
    start carried in `tasks.alpha0`."""
    if polish_schedule is not None:
        return solve_polished(factor, tasks, config, polish_schedule,
                              stream=stream, stream_config=stream_config,
                              solve_fn=solve_fn, gap_trace=False)
    if route_stage2(factor, tasks, stream, stream_config, solve_fn,
                    solve_batch):
        return solve_streamed_auto(factor.G, tasks, config,
                                   stream_config=stream_config)
    return solve_fn(factor.G, tasks, config)


def kfold_masks(n: int, k: int, seed: int = 0) -> List[np.ndarray]:
    """Return k boolean validation masks partitioning range(n)."""
    perm = np.random.default_rng(seed).permutation(n)
    masks = []
    for f in range(k):
        m = np.zeros(n, dtype=bool)
        m[perm[f::k]] = True
        masks.append(m)
    return masks


def build_cv_tasks(
    labels: np.ndarray,
    n_classes: int,
    C: float,
    val_masks: Sequence[np.ndarray],
    *,
    n_pad: Optional[int] = None,
    warm: Optional[jnp.ndarray] = None,
) -> Tuple[TaskBatch, list]:
    """Stack OVO tasks for every fold into one batch of T = folds * pairs.

    Task layout: fold-major (fold f, pair t) -> row f * n_pairs + t, so a warm
    start from a previous C value can be passed straight through as `warm`.
    """
    batches, pairs = [], None
    # Pad all folds to a common width so batches stack.
    if n_pad is None:
        counts = np.bincount(labels, minlength=n_classes)
        top2 = np.sort(counts)[-2:].sum()
        n_pad = -(-int(top2) // 8) * 8
    for vm in val_masks:
        tb, pairs = build_ovo_tasks(labels, n_classes, C,
                                    include_mask=~vm, n_pad=n_pad)
        batches.append(tb)
    tasks = TaskBatch(
        idx=jnp.concatenate([b.idx for b in batches]),
        y=jnp.concatenate([b.y for b in batches]),
        c=jnp.concatenate([b.c for b in batches]),
        alpha0=(jnp.clip(warm, 0.0, C) if warm is not None
                else jnp.concatenate([b.alpha0 for b in batches])),
    )
    return tasks, pairs


def _fold_val_sets(factor: LowRankFactor, labels: np.ndarray,
                   val_masks: Sequence[np.ndarray]) -> List[tuple]:
    """Hoisted per-fold validation features: the `np.where(vm)[0]` index and
    the G validation-row gather are computed ONCE per gamma here instead of
    once per (gamma, C) cell inside the C loop."""
    return [(factor.G[np.where(vm)[0]], labels[vm]) for vm in val_masks]


def _cv_error_from(val_sets: Sequence[tuple], n_classes: int,
                   W: jnp.ndarray) -> float:
    """Validation error of one (gamma, C) cell from pre-gathered fold sets."""
    pairs = class_pairs(n_classes)
    n_pairs = len(pairs)
    wrong = 0
    total = 0
    for f, (Gv, yv) in enumerate(val_sets):
        Wf = W[f * n_pairs:(f + 1) * n_pairs]
        dec = np.asarray(Gv @ Wf.T)
        pred = (ovo_vote(dec, pairs, n_classes) if n_pairs > 1
                else np.where(dec[:, 0] > 0, 0, 1))
        wrong += int(np.sum(pred != yv))
        total += len(yv)
    return wrong / max(total, 1)


def _cv_error(factor: LowRankFactor, labels: np.ndarray, n_classes: int,
              W: jnp.ndarray, val_masks: Sequence[np.ndarray]) -> float:
    """Validation error using precomputed G rows as features (no kernel evals)."""
    return _cv_error_from(_fold_val_sets(factor, labels, val_masks),
                          n_classes, W)


def build_cv_grid_tasks(
    labels: np.ndarray,
    n_classes: int,
    Cs: Sequence[float],
    val_masks: Sequence[np.ndarray],
    *,
    n_pad: Optional[int] = None,
    warm: Optional[jnp.ndarray] = None,
    ladder: bool = True,
) -> Tuple[TaskBatch, list, Optional[np.ndarray]]:
    """One TaskBatch carrying EVERY (C, fold, pair) cell of a gamma.

    Level-major layout on top of `build_cv_tasks`' fold-major one: cell
    (ci, f, t) is task  u = ci * folds * n_pairs + f * n_pairs + t,  so
    slicing ``ci * FP:(ci + 1) * FP`` (FP = folds * n_pairs) recovers one
    C value's batch in exactly the per-cell layout.

    ``Cs`` must be ascending.  With ``ladder=True`` the returned
    ``chain_next`` declares each cell the warm-start predecessor of the same
    (fold, pair) cell at the next C — the paper's C-ladder warm start,
    executed inside the streamed engine (`solver_stream`) so the whole grid
    trains in one G stream.  ``warm`` seeds level 0 (cross-gamma warm
    start), clipped into the first C box by `build_cv_tasks`.
    """
    Cs = [float(C) for C in Cs]
    if sorted(Cs) != Cs:
        raise ValueError("build_cv_grid_tasks requires ascending Cs")
    if n_pad is None:
        counts = np.bincount(labels, minlength=n_classes)
        top2 = np.sort(counts)[-2:].sum()
        n_pad = -(-int(top2) // 8) * 8
    levels, pairs = [], None
    for ci, C in enumerate(Cs):
        tb, pairs = build_cv_tasks(labels, n_classes, C, val_masks,
                                   n_pad=n_pad,
                                   warm=warm if ci == 0 else None)
        levels.append(tb)
    tasks = TaskBatch(
        idx=jnp.concatenate([b.idx for b in levels]),
        y=jnp.concatenate([b.y for b in levels]),
        c=jnp.concatenate([b.c for b in levels]),
        alpha0=jnp.concatenate([b.alpha0 for b in levels]),
    )
    chain = None
    FP = len(val_masks) * len(pairs)
    if ladder and len(Cs) > 1:
        chain = np.full((len(Cs) * FP,), -1, np.int64)
        chain[:(len(Cs) - 1) * FP] = np.arange((len(Cs) - 1) * FP) + FP
    return tasks, pairs, chain


@dataclasses.dataclass
class GridResult:
    errors: np.ndarray            # (n_gamma, n_C) CV error
    best_gamma: float
    best_C: float
    best_error: float
    stage1_seconds: float
    stage2_seconds: float
    n_binary_solved: int
    per_cell_seconds: np.ndarray  # (n_gamma, n_C)
    stream_stats: Optional[list] = None
    # ^ farm path: one Stage2StreamStats per gamma — the whole (C x folds)
    #   grid of that gamma trained in the one stream it records, so "one
    #   pass set per grid" is assertable, not just timed
    bytes_h2d: Optional[np.ndarray] = None   # (n_gamma,) farm H2D bytes


def grid_search(
    x: np.ndarray,
    y: np.ndarray,
    gammas: Sequence[float],
    Cs: Sequence[float],
    *,
    budget: int = 500,
    folds: int = 5,
    kernel_kind: str = "rbf",
    config: SolverConfig = SolverConfig(),
    seed: int = 0,
    gram_fn: Callable = gram,
    solve_fn: Callable = solve_batch,
    warm_start: bool = True,
    warm_start_gamma: bool = False,
    stream: Optional[bool] = None,
    stream_config: Optional[StreamConfig] = None,
    polish: bool = False,
    polish_levels: int = 3,
    polish_schedule: Optional[PolishSchedule] = None,
    farm: Optional[bool] = None,
) -> GridResult:
    """Full grid search with k-fold CV, G reuse per gamma, warm starts over C.

    Cs are solved in ascending order so each cell warm-starts from its
    predecessor (alphas clipped into the new box).

    ``farm`` selects the grid TASK FARM: every (C, fold, pair) cell of a
    gamma rides ONE streamed TaskBatch (`build_cv_grid_tasks`) with the
    C-ladder warm starts executed inside the engine (`chain_next`), so each
    streamed G block updates every live grid cell before eviction and the
    grid costs ~one training pass of H2D instead of |Cs| pass sets.  The
    default (``None``) routes onto the farm exactly when the cells would
    stream anyway (`route_stage2`) and no polish ladder is requested;
    ``True`` forces it, ``False`` pins the per-cell serial loop.

    ``warm_start_gamma`` (beyond-paper): also seed the first C of each new
    gamma from the previous gamma's alphas at the same C.  The dual variables
    stay feasible (same box, same task layout); only the geometry changed, so
    nearby gammas start close to optimal.  The paper warm-starts only across
    C (sec. 4).

    ``polish`` runs every cell through the coarse-to-fine ladder
    (`core/polish.py`); it composes with both warm-start axes — the carried
    alphas seed the ladder's coarse levels too — and selects the same cell
    (the error surface is unchanged, only the trajectory is cheaper).
    """
    x = np.asarray(x, np.float32)
    classes, labels = np.unique(np.asarray(y), return_inverse=True)
    n_classes = len(classes)
    val_masks = kfold_masks(x.shape[0], folds, seed)
    Cs = sorted(float(c) for c in Cs)
    if polish and polish_schedule is None:
        polish_schedule = make_schedule(levels=polish_levels)

    errors = np.zeros((len(gammas), len(Cs)))
    cell_sec = np.zeros_like(errors)
    t_stage1 = 0.0
    t_stage2 = 0.0
    n_solved = 0
    best = (np.inf, None, None)
    gamma_stats: List = [None] * len(gammas)
    gamma_bytes = np.zeros((len(gammas),), np.int64)

    tr = resolve_tracer(getattr(stream_config, "trace", None))
    warm_first_c = None       # cross-gamma seed (beyond-paper)
    for gi, gamma in enumerate(gammas):
        kp = KernelParams(kind=kernel_kind, gamma=float(gamma))
        # Each gamma is its own resumable unit: G and the solver state both
        # depend on gamma, so checkpoints — and spilled-G shard stores,
        # whose contents are a function of gamma — live in per-gamma
        # subdirs (the snapshot's G fingerprint rejects any cross-gamma
        # mixup anyway).
        g_cfg = stream_config
        ck = getattr(stream_config, "checkpoint_dir", None)
        sd = getattr(stream_config, "shard_dir", None)
        if ck or sd:
            g_cfg = dataclasses.replace(
                stream_config,
                checkpoint_dir=os.path.join(ck, f"gamma{gi}") if ck else None,
                shard_dir=os.path.join(sd, f"gamma{gi}") if sd else None)
        t0 = tr.begin("cv", "stage1_factor")
        factor = compute_factor(x, kp, budget,
                                key=jax.random.PRNGKey(seed), gram_fn=gram_fn,
                                stream=stream, stream_config=g_cfg)
        wait_for_factor(factor.G)
        t_stage1 += tr.end(t0, gamma=float(gamma))

        warm = warm_first_c if warm_start_gamma else None
        use_farm = False
        if farm is not False and polish_schedule is None and len(Cs) > 1:
            gtasks, pairs, chain = build_cv_grid_tasks(
                labels, n_classes, Cs, val_masks,
                warm=warm if warm_start else None,
                ladder=warm_start)
            use_farm = (farm is True
                        or route_stage2(factor, gtasks, stream, stream_config,
                                        solve_fn, solve_batch))
        if use_farm:
            # Grid task farm: one streamed solve trains every (C, fold,
            # pair) cell of this gamma — the C-ladder runs inside the
            # engine, so the epoch budget covers the whole ladder (the +1
            # per level pays each seeded cell's w0-accumulation pass).
            t0 = tr.begin("cv", "grid_farm")
            FP = folds * len(pairs)
            farm_cfg = dataclasses.replace(
                config, max_epochs=config.max_epochs * len(Cs) + len(Cs))
            res, sstats = solve_streamed_auto(
                factor.G, gtasks, farm_cfg, stream_config=g_cfg,
                chain_next=chain, return_stats=True)
            wait_for_factor(res.w)
            dt = tr.end(t0, gamma=float(gamma), cells=gtasks.n_tasks)
            t_stage2 += dt
            cell_sec[gi, :] = dt / len(Cs)
            n_solved += gtasks.n_tasks
            gamma_stats[gi] = sstats
            gamma_bytes[gi] = sstats.bytes_h2d
            val_sets = _fold_val_sets(factor, labels, val_masks)
            W = np.asarray(res.w)
            for ci, C in enumerate(Cs):
                err = _cv_error_from(val_sets, n_classes,
                                     W[ci * FP:(ci + 1) * FP])
                errors[gi, ci] = err
                if err < best[0]:
                    best = (err, float(gamma), C)
            warm_first_c = np.asarray(res.alpha)[:FP]
            continue

        val_sets = _fold_val_sets(factor, labels, val_masks)
        for ci, C in enumerate(Cs):
            t0 = tr.begin("cv", "grid_cell")
            tasks, _ = build_cv_tasks(labels, n_classes, C, val_masks,
                                      warm=warm if warm_start else None)
            c_cfg = g_cfg
            if getattr(g_cfg, "checkpoint_dir", None):  # checkpointing: each C
                c_cfg = dataclasses.replace(  # cell is its own resumable unit
                    g_cfg, checkpoint_dir=os.path.join(g_cfg.checkpoint_dir,
                                                       f"c{ci}"))
            res = _solve_routed(factor, tasks, config, solve_fn,
                                stream, c_cfg, polish_schedule)
            wait_for_factor(res.w)
            dt = tr.end(t0, gamma=float(gamma), C=float(C))
            t_stage2 += dt
            cell_sec[gi, ci] = dt
            n_solved += tasks.n_tasks
            warm = res.alpha
            if ci == 0:
                warm_first_c = res.alpha
            err = _cv_error_from(val_sets, n_classes, res.w)
            errors[gi, ci] = err
            if err < best[0]:
                best = (err, float(gamma), C)

    farmed = any(s is not None for s in gamma_stats)
    return GridResult(
        errors=errors, best_gamma=best[1], best_C=best[2], best_error=best[0],
        stage1_seconds=t_stage1, stage2_seconds=t_stage2,
        n_binary_solved=n_solved, per_cell_seconds=cell_sec,
        stream_stats=gamma_stats if farmed else None,
        bytes_h2d=gamma_bytes if farmed else None,
    )


def cross_validate(
    x: np.ndarray, y: np.ndarray, kernel: KernelParams, C: float, *,
    budget: int = 500, folds: int = 5, config: SolverConfig = SolverConfig(),
    seed: int = 0, gram_fn: Callable = gram, solve_fn: Callable = solve_batch,
    factor: Optional[LowRankFactor] = None,
    stream: Optional[bool] = None,
    stream_config: Optional[StreamConfig] = None,
    polish_schedule: Optional[PolishSchedule] = None,
) -> Tuple[float, LowRankFactor]:
    """k-fold CV error for one (kernel, C); returns (error, reusable factor)."""
    x = np.asarray(x, np.float32)
    _, labels = np.unique(np.asarray(y), return_inverse=True)
    n_classes = int(labels.max()) + 1
    if factor is None:
        factor = compute_factor(x, kernel, budget,
                                key=jax.random.PRNGKey(seed), gram_fn=gram_fn,
                                stream=stream, stream_config=stream_config)
    val_masks = kfold_masks(x.shape[0], folds, seed)
    tasks, _ = build_cv_tasks(labels, n_classes, float(C), val_masks)
    res = _solve_routed(factor, tasks, config, solve_fn, stream, stream_config,
                        polish_schedule)
    err = _cv_error(factor, labels, n_classes, res.w, val_masks)
    return err, factor
