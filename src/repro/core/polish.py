"""Polishing: coarse-to-fine warm-started stage-2 training (the paper title's
first ingredient).

The paper trains an approximate predictor cheaply and then *polishes* it:
rather than cold-starting the full-data solve at the final tolerance, a
ladder of nested row-subsample problems (e.g. n/16 -> n/4 -> n) is solved
with per-level tolerance annealing, each level warm-starting the next.  The
expensive full-data pass then starts near the optimum and is a short polish
instead of a full optimization — the same reuse pattern `core/cv.py`
exploits for C grids (paper Table 3), applied along the data axis (cf.
Tyree et al., arXiv:1404.1066, where coarse-then-refine dominates cold
parallel solves).

Mechanics per level:

  * **restriction** — each task keeps a nested, class-stratified random
    prefix of its real (c > 0) rows; the union of kept rows over the task
    batch is gathered into a compact level factor `G[union]`, so coarse
    levels stay monolithic on device even when the full G is a host-resident
    streamed buffer;
  * **solve** — the routed solver: `solve_batch` (or an injected
    `solve_fn`) for levels that fit the device budget, `solve_batch_streamed`
    when they do not; the FINAL level goes through the exact same
    `route_stage2` predicate as an unpolished fit, so a streamed fit still
    streams where it matters;
  * **prolongation** — the level's solved alphas are scattered back into the
    task's full index space (clipped to the box); rows not yet seen keep
    their incoming warm start (so C-grid warm starts compose: coarse levels
    start from the previous C's solution too).  The next level's w is
    recomputed from the prolonged alpha by the solver (`w0 = (a0*y) @ G`),
    which is exactly the dual-feasible prolongation.

The ladder also overrides the solver's full-pass verification cadence
(`PolishSchedule.full_pass_period`, default 1): warm-started levels converge
in a handful of passes, and the cold solver's 20-epoch shrinking cadence
would quantise every level to >= 21 epochs.  `benchmarks/polish.py` records
a period-1 cold baseline alongside, so the cadence effect is never silently
attributed to the warm starts.

When it pays: problems where a subsample's solution transfers — the
near-separable, few-SV regime of good (deep) features, the paper's ImageNet
setting.  Fine-structure problems (sharp-gamma checkerboards) transfer
coarse solutions poorly and break even.  Either way correctness is
unchanged: the final level enforces the same KKT tolerance as a cold solve,
so the polished solution is duality-gap-matched (tests/test_polish.py).

Everything is bookkeeping over the existing solvers — the subsystem adds a
control layer, not new numerics.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.dual_solver import (SolveResult, SolverConfig, TaskBatch,
                                    solve_batch)
from repro.core.solver_stream import (Stage2StreamStats, route_stage2,
                                      should_stream_stage2,
                                      solve_batch_streamed,
                                      solve_streamed_auto)
from repro.core.streaming import StreamConfig
from repro.core.trace import resolve as resolve_tracer


@dataclasses.dataclass(frozen=True)
class PolishSchedule:
    """The coarse-to-fine ladder: ascending row fractions (last one must be
    1.0 — the full-data polish pass) with per-level tolerance annealing
    (`tol * tol_factor`, final factor 1.0 = `SolverConfig.tol`)."""

    fractions: Tuple[float, ...] = (1 / 16, 1 / 4, 1.0)
    tol_factors: Tuple[float, ...] = (16.0, 4.0, 1.0)
    min_rows: int = 64     # per-task floor: coarse levels never degenerate
    seed: int = 0          # row-priority RNG (nested prefixes)
    scale_C: bool = False  # True scales the coarse box by n/m (constant
                           # lambda = 1/(C n)); False keeps the paper's
                           # unnormalised C * sum(hinge) objective per level
    full_pass_period: Optional[int] = 1
                           # override SolverConfig.full_pass_period for
                           # MONOLITHIC level solves: every jit epoch costs
                           # the same, warm-started levels converge in a
                           # handful of passes, and the stock 20-epoch
                           # verification cadence would quantise every level
                           # to >= 21 epochs (None = keep the config's)
    stream_full_pass_period: Optional[int] = 5
                           # override for STREAMED level solves: cheap epochs
                           # are the point there (shrinking compacts H2D
                           # bytes), but the cold 20-epoch cadence still
                           # over-quantises a warm-started polish pass; 5
                           # balances verification latency against
                           # compaction (None = keep the config's)

    def __post_init__(self):
        if len(self.fractions) != len(self.tol_factors):
            raise ValueError("fractions and tol_factors must align")
        if not self.fractions or abs(self.fractions[-1] - 1.0) > 1e-9:
            raise ValueError("last level must be the full data (fraction 1.0)")
        if any(f <= 0.0 or f > 1.0 for f in self.fractions):
            raise ValueError("fractions must lie in (0, 1]")
        if any(b <= a for a, b in zip(self.fractions, self.fractions[1:])):
            raise ValueError("fractions must be strictly ascending")
        if any(f < 1.0 for f in self.tol_factors):
            raise ValueError("tol_factors anneal TOWARD tol; need >= 1")

    @property
    def n_levels(self) -> int:
        return len(self.fractions)


def make_schedule(levels: int = 3, ratio: float = 4.0, tol_growth: float = 4.0,
                  min_rows: int = 64, seed: int = 0,
                  scale_C: bool = False,
                  full_pass_period: Optional[int] = 1,
                  stream_full_pass_period: Optional[int] = 5) -> PolishSchedule:
    """Geometric ladder: fractions ratio^-(L-1) ... 1, tols tol*growth^(L-1)
    ... tol (levels=3, ratio=4 -> the paper-style n/16 -> n/4 -> n).

    The default ``full_pass_period=1`` makes every ladder epoch a full
    verification pass: warm-started levels stop the moment they are KKT-
    converged instead of waiting out the cold solver's 20-epoch cadence.
    """
    if levels < 1:
        raise ValueError("need at least one level")
    fr = tuple(float(ratio) ** -(levels - 1 - l) for l in range(levels))
    tf = tuple(float(tol_growth) ** (levels - 1 - l) for l in range(levels))
    return PolishSchedule(fractions=fr, tol_factors=tf, min_rows=min_rows,
                          seed=seed, scale_C=scale_C,
                          full_pass_period=full_pass_period,
                          stream_full_pass_period=stream_full_pass_period)


@dataclasses.dataclass
class PolishLevelStats:
    """Convergence + work accounting of one ladder level."""

    fraction: float
    tol: float
    n_rows: int                   # union of task rows gathered at this level
    n_pad: int
    streamed: bool
    epochs: np.ndarray            # (T,)
    violations: np.ndarray        # (T,)
    duality_gap: np.ndarray       # (T,) nan when gap_trace=False
    row_visits: int               # coordinate visits charged to this level
    seconds: float
    stream_stats: Optional[Stage2StreamStats] = None


@dataclasses.dataclass
class PolishTrace:
    """Per-level trajectory of one polished solve (FitStats.polish_trace)."""

    levels: List[PolishLevelStats] = dataclasses.field(default_factory=list)

    @property
    def total_row_visits(self) -> int:
        return sum(l.row_visits for l in self.levels)

    @property
    def total_seconds(self) -> float:
        return sum(l.seconds for l in self.levels)

    @property
    def final(self) -> PolishLevelStats:
        return self.levels[-1]


def task_duality_gap(rows, y, c, alpha) -> float:
    """Host-side duality gap of one task from its gathered G rows (numpy, so
    a streamed host-resident G is never device-materialised for the trace);
    mirrors `dual_solver.duality_gap`."""
    rows = np.asarray(rows, np.float32)
    y = np.asarray(y, np.float32)
    c = np.asarray(c, np.float32)
    alpha = np.asarray(alpha, np.float32)
    w = (alpha * y) @ rows
    real = c > 0.0
    C = float(c.max()) if real.any() else 1.0
    margins = y * (rows @ w)
    hinge = np.where(real, np.maximum(0.0, 1.0 - margins), 0.0)
    p = 0.5 * float(w @ w) + C * float(hinge.sum())
    d = float(alpha.sum()) - 0.5 * float(w @ w)
    return p - d


def _level_positions(idx: np.ndarray, y: np.ndarray, c: np.ndarray,
                     schedule: PolishSchedule, n_rows: int) -> List[List[np.ndarray]]:
    """Per (level, task): positions into the PADDED task layout, sorted by
    global row index.  Selection is a class-stratified random prefix under a
    fixed per-row priority, so levels are nested (coarse rows never leave)
    and idx stays sorted — the streamed solver's contract."""
    T = idx.shape[0]
    prio = np.random.default_rng(schedule.seed).random(n_rows)
    floor_p = schedule.min_rows // 2
    floor_n = schedule.min_rows - floor_p
    sel: List[List[np.ndarray]] = [[None] * T for _ in schedule.fractions]
    for t in range(T):
        real_pos = np.where(c[t] > 0.0)[0]
        rt = idx[t][real_pos]
        yt = y[t][real_pos]
        pr = prio[rt]
        pos_p = np.where(yt > 0)[0]
        pos_n = np.where(yt <= 0)[0]
        ord_p = pos_p[np.argsort(pr[pos_p], kind="stable")]
        ord_n = pos_n[np.argsort(pr[pos_n], kind="stable")]
        for li, f in enumerate(schedule.fractions):
            if f >= 1.0:
                sl = np.arange(len(real_pos))
            else:
                kp = min(len(ord_p), max(math.ceil(f * len(ord_p)), floor_p))
                kn = min(len(ord_n), max(math.ceil(f * len(ord_n)), floor_n))
                sl = np.sort(np.concatenate([ord_p[:kp], ord_n[:kn]]))
            sel[li][t] = real_pos[sl]
    return sel


def _route_level(n_rows: int, rank: int, n_tasks: int, n_pad: int,
                 stream, stream_config: Optional[StreamConfig],
                 solve_fn: Callable) -> bool:
    """Routing for a COARSE level: the gathered sub-factor is its own
    problem, so only its own working set decides — a forced `stream=True`
    streams the final level (via `route_stage2`) but must not force tiny
    gathered levels off device."""
    if solve_fn is not solve_batch or stream is False or stream_config is None:
        return False
    return should_stream_stage2(n_rows, rank, n_tasks, n_pad, stream_config)


def solve_polished(
    factor,
    tasks: TaskBatch,
    config: SolverConfig = SolverConfig(),
    schedule: Optional[PolishSchedule] = None,
    *,
    stream=None,
    stream_config: Optional[StreamConfig] = None,
    solve_fn: Callable = solve_batch,
    gap_trace: bool = True,
    return_trace: bool = False,
    trace=None,
):
    """Coarse-to-fine warm-started drop-in for the routed stage-2 solve.

    Solves the schedule's nested subsample ladder, prolongating each level's
    alpha into the next, and returns the FINAL level's `SolveResult` (same
    shapes/layout as `solve_batch(factor.G, tasks, config)`), plus a
    `PolishTrace` when ``return_trace=True``.  Incoming `tasks.alpha0` (the
    C-grid warm start) seeds every level's not-yet-solved rows.
    """
    if schedule is None:
        schedule = PolishSchedule()
    G = factor.G
    n, rank = int(G.shape[0]), int(G.shape[1])
    host_G = isinstance(G, np.ndarray)
    idx = np.asarray(tasks.idx)
    y_loc = np.asarray(tasks.y, np.float32)
    c_loc = np.asarray(tasks.c, np.float32)
    T, n_pad = idx.shape
    af = np.clip(np.asarray(tasks.alpha0, np.float32), 0.0, c_loc)

    # `trace` observes only; level ROUTING still keys off `stream_config`
    tr = resolve_tracer(trace if trace is not None
                        else getattr(stream_config, "trace", None))
    sel = _level_positions(idx, y_loc, c_loc, schedule, n)
    # Drop redundant coarse levels (min_rows flooring can make a level equal
    # its successor; nested prefixes => equal sizes means equal sets).
    keep = [li for li in range(schedule.n_levels - 1)
            if any(len(sel[li][t]) < len(sel[li + 1][t]) for t in range(T))]
    keep.append(schedule.n_levels - 1)

    trace = PolishTrace()
    res: Optional[SolveResult] = None

    def _level_config(li: int, streamed: bool) -> SolverConfig:
        period = (schedule.stream_full_pass_period if streamed
                  else schedule.full_pass_period) or config.full_pass_period
        return dataclasses.replace(
            config, tol=float(config.tol * schedule.tol_factors[li]),
            full_pass_period=period)

    for li in keep:
        frac = schedule.fractions[li]
        final = frac >= 1.0
        t0 = tr.begin("polish", f"level_{li}")
        sstats = None
        if final:
            tasks_l = TaskBatch(idx=tasks.idx, y=tasks.y, c=tasks.c,
                                alpha0=jnp.asarray(np.clip(af, 0.0, c_loc)))
            streamed = route_stage2(factor, tasks_l, stream, stream_config,
                                    solve_fn, solve_batch)
            cfg_l = _level_config(li, streamed)
            if streamed:
                # Final level: the full-size stream — overlapped over every
                # local device when there are several (shared block reader).
                res, sstats = solve_streamed_auto(
                    G, tasks_l, cfg_l, stream_config=stream_config,
                    return_stats=True)
            else:
                res = solve_fn(jnp.asarray(G) if host_G else G, tasks_l, cfg_l)
            af = np.asarray(res.alpha)
            res_l, n_pad_l, n_rows_l = res, n_pad, n
            pos_l = sel[li]
            level_G = G          # gap rows gathered lazily below
        else:
            pos_l = sel[li]
            n_pad_l = max(8, -(-max(len(p) for p in pos_l) // 8) * 8)
            union = np.unique(np.concatenate(
                [idx[t][p] for t, p in enumerate(pos_l)]))
            n_rows_l = len(union)
            level_G = G[union]      # host gather (np G) or device gather (jnp)
            idx_l = np.zeros((T, n_pad_l), np.int32)
            y_l = np.ones((T, n_pad_l), np.float32)
            c_l = np.zeros((T, n_pad_l), np.float32)
            a_l = np.zeros((T, n_pad_l), np.float32)
            for t, p in enumerate(pos_l):
                k = len(p)
                m_full = int(np.sum(c_loc[t] > 0.0))
                scale = (m_full / max(k, 1)) if schedule.scale_C else 1.0
                idx_l[t, :k] = np.searchsorted(union, idx[t][p])
                y_l[t, :k] = y_loc[t][p]
                c_l[t, :k] = c_loc[t][p] * scale
                a_l[t, :k] = np.clip(af[t][p], 0.0, c_l[t, :k])
            tasks_l = TaskBatch(idx=jnp.asarray(idx_l), y=jnp.asarray(y_l),
                                c=jnp.asarray(c_l), alpha0=jnp.asarray(a_l))
            streamed = _route_level(n_rows_l, rank, T, n_pad_l, stream,
                                    stream_config, solve_fn)
            cfg_l = _level_config(li, streamed)
            if streamed:
                res_l, sstats = solve_batch_streamed(
                    np.asarray(level_G), tasks_l, cfg_l,
                    stream_config=stream_config, return_stats=True)
            else:
                res_l = solve_fn(jnp.asarray(level_G) if host_G else level_G,
                                 tasks_l, cfg_l)
            # prolongation: solved rows overwrite (raw, in the level's scaled
            # box — each use site clips into its own box); unseen rows keep
            # their incoming warm start
            a_res = np.asarray(res_l.alpha)
            for t, p in enumerate(pos_l):
                af[t][p] = a_res[t][: len(p)]

        visits = (sstats.coord_visits if sstats is not None
                  else int(np.asarray(res_l.epochs).sum()) * n_pad_l)
        gaps = np.full((T,), np.nan, np.float32)
        if gap_trace and final and not host_G:
            # device-resident G: compute the gap on device (scalars back)
            # instead of copying the full (n, B) factor to host
            from repro.core.dual_solver import duality_gap as _gap_dev
            for t in range(T):
                gaps[t] = float(_gap_dev(G, tasks.idx[t], tasks.y[t],
                                         tasks.c[t],
                                         jnp.asarray(res_l.alpha)[t]))
        elif gap_trace:
            # host numpy path: coarse levels use the small gathered factor;
            # a streamed final level must never device-materialise G
            G_np = level_G if isinstance(level_G, np.ndarray) \
                else np.asarray(level_G)
            a_np = np.asarray(res_l.alpha)
            for t, p in enumerate(pos_l):
                k = len(p)
                if final:
                    gaps[t] = task_duality_gap(G_np[idx[t][p]], y_loc[t][p],
                                               c_loc[t][p], a_np[t][p])
                else:
                    # the LEVEL's own problem (scaled box): that is the
                    # quantity the tolerance annealing drives toward zero
                    gaps[t] = task_duality_gap(G_np[idx_l[t, :k]], y_l[t, :k],
                                               c_l[t, :k], a_np[t][:k])
        dt = tr.end(t0, fraction=float(frac), tol=float(cfg_l.tol),
                    rows=n_rows_l, streamed=streamed, row_visits=visits)
        trace.levels.append(PolishLevelStats(
            fraction=frac, tol=cfg_l.tol, n_rows=n_rows_l, n_pad=n_pad_l,
            streamed=streamed, epochs=np.asarray(res_l.epochs),
            violations=np.asarray(res_l.violation), duality_gap=gaps,
            row_visits=visits, seconds=dt,
            stream_stats=sstats))

    return (res, trace) if return_trace else res
