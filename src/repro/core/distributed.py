"""Distribution layer for LPD-SVM on a TPU mesh.

Two parallelism patterns, mirroring the paper's hardware mapping (sec. 4):

1. **Stage 1 is dense-linear-algebra parallel** — the paper runs it on GPUs
   with cuBLAS/cuSOLVER.  Here the gram rows are sharded over the mesh
   ("data" x optionally "pod"), the budget axis over "model", and the B x B
   eigendecomposition is replicated (B <= 10^4, same as the paper's single-GPU
   eig).  `stage1_steps` exposes the jit-able pieces with shardings for the
   dry-run.

2. **Stage 2 is a task farm** — one binary problem is sequential, but OVO
   pairs x CV folds x grid cells give thousands of independent tasks ("far
   more parallelism than we need to fully exploit even multiple GPUs").
   `solve_tasks_sharded` shards the task axis over every mesh device via
   shard_map; each device vmaps its local chunk.  G is replicated (it is the
   shared read-only factor; per-chip HBM plays the paper's 512 GB RAM role).
   When G must stay in HOST RAM, `solve_tasks_streamed` is the out-of-core
   farm: the task axis is split over local devices balanced by active-row
   count, and one shared host reader streams each G row-block ONCE per pass,
   fanning it out to per-device worker queues so H2D/compute/D2H overlap
   across devices — the paper's "many cores driving multiple GPUs out of a
   large-RAM host" hardware mapping.

Both work unchanged on a single-device mesh (tests) and the production
16x16 / 2x16x16 meshes (dry-run).
"""
from __future__ import annotations

import math
import queue
import threading
import time
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.dual_solver import SolveResult, SolverConfig, TaskBatch, solve_batch
from repro.core.faults import classify_error
from repro.core.kernel_fn import KernelParams, apply_epilogue
from repro.core.resilience import WatchdogTimeout, WorkerStuckError
from repro.core.trace import resolve as resolve_tracer


def _mesh_size(mesh: Mesh, axes: Sequence[str]) -> int:
    return math.prod(mesh.shape[a] for a in axes)


def pad_tasks(tasks: TaskBatch, multiple: int) -> Tuple[TaskBatch, int]:
    """Pad the task axis to a device-count multiple with inert (c=0) tasks."""
    T = tasks.n_tasks
    T_pad = -(-T // multiple) * multiple
    if T_pad == T:
        return tasks, T
    pad = T_pad - T

    def padT(a):
        return jnp.concatenate([a, jnp.zeros((pad,) + a.shape[1:], a.dtype)])

    return TaskBatch(padT(tasks.idx), padT(tasks.y), padT(tasks.c),
                     padT(tasks.alpha0)), T


def solve_tasks_sharded(
    G: jnp.ndarray,
    tasks: TaskBatch,
    config: SolverConfig,
    mesh: Mesh,
    task_axes: Optional[Sequence[str]] = None,
) -> SolveResult:
    """Solve a TaskBatch with the task axis sharded over the whole mesh."""
    if task_axes is None:
        task_axes = tuple(mesh.axis_names)
    task_axes = tuple(task_axes)
    n_dev = _mesh_size(mesh, task_axes)
    tasks, T = pad_tasks(tasks, n_dev)

    tspec = P(task_axes)

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(None, None), tspec, tspec, tspec, tspec),
        out_specs=SolveResult(tspec, tspec, P(task_axes), P(task_axes),
                              P(task_axes), P(task_axes)),
        check_vma=False,   # solver carries mix invariant consts with varying data
    )
    def farm(G, idx, y, c, a0):
        return solve_batch(G, TaskBatch(idx, y, c, a0), config)

    res = farm(G, tasks.idx, tasks.y, tasks.c, tasks.alpha0)
    # strip task padding
    return SolveResult(*(r[:T] for r in res))


def balance_task_split(row_counts: Sequence[int],
                       n_parts: int) -> List[np.ndarray]:
    """Partition tasks over ``n_parts`` devices balanced by ACTIVE-ROW count.

    The old ``np.linspace`` split balanced task COUNT, so one fat OVO pair
    (two majority classes) serialised the whole farm behind its device.  LPT
    greedy instead: tasks sorted by row count descending, each assigned to
    the currently lightest part — a classic 4/3-approximation of the optimal
    makespan, deterministic for a given count vector.  Empty parts are
    dropped; each part is returned as a sorted task-index array.
    """
    counts = np.asarray(row_counts, np.int64)
    order = np.argsort(-counts, kind="stable")
    loads = np.zeros(max(1, n_parts), np.int64)
    parts: List[List[int]] = [[] for _ in range(max(1, n_parts))]
    for t in order:
        k = int(np.argmin(loads))
        parts[k].append(int(t))
        loads[k] += max(int(counts[t]), 1)   # inert tasks still spread
    return [np.sort(np.asarray(p, np.int64)) for p in parts if p]


def balance_chain_split(row_counts: Sequence[int], chain_next,
                        n_parts: int) -> List[np.ndarray]:
    """`balance_task_split` over C-ladder CHAINS instead of single tasks.

    A chain (task t, its `chain_next[t]` successor, and so on) must stay on
    ONE device: the successor is seeded from the predecessor's alphas inside
    the engine at convergence time.  Chains are therefore the atomic unit of
    the LPT split, weighted by the sum of their members' row counts — a
    chain runs its levels sequentially, so its load is the whole ladder's.
    Returns sorted task-index arrays like `balance_task_split`.
    """
    counts = np.asarray(row_counts, np.int64)
    nxt = np.asarray(chain_next, np.int64)
    has_pred = np.zeros(len(counts), bool)
    for s in nxt:
        if s >= 0:
            has_pred[s] = True
    chains: List[List[int]] = []
    for t in range(len(counts)):
        if has_pred[t]:
            continue
        chain, u = [], t
        while u >= 0:
            chain.append(u)
            u = int(nxt[u])
        chains.append(chain)
    weights = [sum(max(int(counts[t]), 1) for t in ch) for ch in chains]
    groups = balance_task_split(weights, n_parts)
    return [np.sort(np.concatenate([np.asarray(chains[int(ci)], np.int64)
                                    for ci in g])) for g in groups]


def _local_chain(chain_next, part: np.ndarray) -> Optional[np.ndarray]:
    """Remap global `chain_next` onto one shard's local task indices."""
    if chain_next is None:
        return None
    nxt = np.asarray(chain_next, np.int64)
    local = {int(g): i for i, g in enumerate(part)}
    return np.array([local.get(int(nxt[int(g)]), -1) for g in part],
                    np.int64)


class _DeviceWorkers:
    """One lightweight host worker per device for the overlapped task farm.

    The shared reader pushes block-feed closures into per-device bounded
    queues; each worker drains its own queue in order, so the per-engine
    block sequence (and hence the SMO trajectory) is preserved while H2D,
    compute, and D2H overlap ACROSS devices.  The bound gives backpressure:
    the reader stalls instead of staging unboundedly many host buffers when
    one device falls behind.  Worker exceptions surface at the next barrier.

    With an enabled tracer the farm's two stall signals become spans: the
    reader's ``queue/backpressure`` (blocked pushing into a full device
    queue — that device is the bottleneck) and each worker's
    ``queue/worker_idle`` (blocked waiting for the reader — the shared
    reader is the bottleneck), plus a per-device queue-depth gauge.

    Fault tolerance: worker errors are recorded WITH the failing device's
    name (`failed()`), so the degradation loop in `solve_tasks_streamed` can
    quarantine exactly the lost devices; ``watchdog`` > 0 turns the barrier
    into a deadline wait that raises a `WatchdogTimeout` full of queue/thread
    diagnostics instead of hanging on a starved queue; `close` detects (and
    reports) workers still alive after the join timeout instead of silently
    leaking them.
    """

    def __init__(self, engines, depth: int, trace=None,
                 names: Optional[Sequence[str]] = None,
                 watchdog: float = 0.0, join_timeout: float = 60.0):
        self._tr = resolve_tracer(trace)
        if names is None:
            names = [f"dev{i}" for i in range(len(engines))]
        self._names = {id(e): nm for e, nm in zip(engines, names)}
        self._queues = {id(e): queue.Queue(maxsize=max(2, depth))
                        for e in engines}
        self._errors: List[Tuple[str, BaseException]] = []
        self._watchdog = watchdog
        self._join_timeout = join_timeout
        # Per-worker last-activity stamp (monotonic seconds + what it was):
        # the watchdog's "who is stuck" diagnostic.
        self._last = {nm: ("spawned", time.monotonic()) for nm in names}
        self._threads = []
        for e in engines:
            nm = self._names[id(e)]
            th = threading.Thread(target=self._loop,
                                  args=(self._queues[id(e)], nm),
                                  name=f"worker/{nm}", daemon=True)
            th.start()
            self._threads.append(th)

    def _loop(self, q, name):
        tr = self._tr
        while True:
            t0 = tr.begin()
            fn = q.get()
            try:
                if fn is None:
                    self._last[name] = ("exited", time.monotonic())
                    return
                if tr.enabled:
                    tr.end("queue", "worker_idle", t0, device=name)
                    tr.counter(f"queue_depth/{name}", q.qsize())
                self._last[name] = ("running", time.monotonic())
                if not self._errors:     # fail fast: drain the rest as no-ops
                    fn()
                self._last[name] = ("idle", time.monotonic())
            except BaseException as exc:   # noqa: BLE001 — re-raised at barrier
                self._errors.append((name, exc))
                self._last[name] = (f"error:{type(exc).__name__}",
                                    time.monotonic())
                # A fault instant (not a span) so a failed run's exported
                # trace shows WHERE the farm broke.
                tr.instant("fault", "worker_error", device=name,
                           error=type(exc).__name__)
            finally:
                q.task_done()

    def submit(self, engine, fn):
        q = self._queues[id(engine)]
        tr = self._tr
        if tr.enabled and q.full():
            # Reader blocked on a full device queue — measured backpressure.
            t0 = tr.begin()
            q.put(fn)
            tr.end("queue", "backpressure", t0,
                   device=self._names[id(engine)])
        else:
            q.put(fn)

    def failed(self):
        """Map of worker name -> first recorded exception (degradation input)."""
        out = {}
        for nm, exc in self._errors:
            out.setdefault(nm, exc)
        return out

    def _diagnose(self) -> str:
        now = time.monotonic()
        lines = []
        for (eid, q), th in zip(self._queues.items(), self._threads):
            nm = th.name.split("/", 1)[-1]
            state, when = self._last.get(nm, ("unknown", now))
            lines.append(f"  {th.name}: alive={th.is_alive()} "
                         f"queued={q.qsize()} unfinished={q.unfinished_tasks} "
                         f"last={state} {now - when:.1f}s ago")
        return "\n".join(lines)

    def barrier(self):
        if self._watchdog > 0:
            deadline = time.monotonic() + self._watchdog
            for q in self._queues.values():
                starved = False
                with q.all_tasks_done:
                    while q.unfinished_tasks:
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            starved = True
                            break
                        q.all_tasks_done.wait(remaining)
                if starved:
                    # raised OUTSIDE the queue lock: _diagnose reads qsize(),
                    # which needs the same (non-reentrant) mutex
                    raise WatchdogTimeout(
                        f"farm barrier starved past {self._watchdog:.1f}s; "
                        "worker states:\n" + self._diagnose())
        else:
            for q in self._queues.values():
                q.join()
        if self._errors:
            raise self._errors[0][1]

    def close(self, suppress: bool = False):
        for q in self._queues.values():
            q.put(None)
        stuck = []
        for th in self._threads:
            th.join(timeout=self._join_timeout)
            if th.is_alive():
                stuck.append(th.name)
        if stuck:
            msg = (f"worker threads still alive after "
                   f"{self._join_timeout:.0f}s join: {', '.join(stuck)}\n"
                   + self._diagnose())
            self._tr.instant("fault", "worker_leak", threads=len(stuck))
            if suppress:
                # Called while an exception propagates (the driver's
                # finally): raising here would REPLACE it — degrade to a
                # warning, the farm is already failing for the real reason.
                import warnings
                warnings.warn(msg, RuntimeWarning, stacklevel=2)
            else:
                raise WorkerStuckError(msg)


def _scatter_results(parts: Sequence[np.ndarray], results, T: int,
                     n_pad: int, rank: int) -> SolveResult:
    """Reassemble per-shard SolveResults into the original task order."""
    alpha = np.zeros((T, n_pad), np.float32)
    w = np.zeros((T, rank), np.float32)
    epochs = np.zeros((T,), np.int32)
    violation = np.zeros((T,), np.float32)
    dual = np.zeros((T,), np.float32)
    n_sv = np.zeros((T,), np.int32)
    for p, r in zip(parts, results):
        alpha[p] = np.asarray(r.alpha)
        w[p] = np.asarray(r.w)
        epochs[p] = np.asarray(r.epochs)
        violation[p] = np.asarray(r.violation)
        dual[p] = np.asarray(r.dual_obj)
        n_sv[p] = np.asarray(r.n_sv)
    return SolveResult(alpha=alpha, w=w, epochs=epochs, violation=violation,
                       dual_obj=dual, n_sv=n_sv)


def solve_tasks_streamed(
    G,
    tasks: TaskBatch,
    config: SolverConfig,
    *,
    devices: Sequence,
    stream_config=None,
    overlap: bool = True,
    return_stats: bool = False,
    epoch_fn=None,
    chain_next=None,
):
    """Out-of-core stage-2 task farm over ``devices`` (host-resident G).

    ``overlap=True`` (default) runs the single-pass shared block broadcast:
    one host reader stages each (tile, B) row-block of G ONCE per shared
    pass and fans it out to every device's bounded in-flight queue
    (`_DeviceWorkers`), so D devices cost one G read per pass — not D — and
    their H2D/compute/D2H pipelines overlap.  ``overlap=False`` keeps the
    legacy serial farm (each device's stream driven to completion in turn,
    re-reading G once per device) as the benchmark baseline.

    The task axis is split by per-task active-row count (`balance_task_split`)
    so one fat OVO pair cannot serialise the farm.  Like
    `stream_factor_over_mesh` this is per-host — a multi-host mesh runs one
    call per process on its local task share (ROADMAP item).

    Each engine owns a PER-DEVICE hot-row block cache (`core/block_cache.py`)
    over its shard's compacted active-row union — unions are shard-local, so
    pinning is too, and warm compacted cheap epochs run with ~zero G H2D on
    every device at once.  Shared full passes never consult the caches: the
    one-read-per-pass reader invariant (per-pass `bytes_h2d` independent of
    device count) is untouched by caching.
    """
    from repro.core.solver_stream import (StreamConfig, _Stage2Engine,
                                          auto_tile_rows, default_epoch_fn,
                                          drive_streamed_engines,
                                          merge_stream_stats,
                                          solve_batch_streamed)

    t0 = time.perf_counter()
    cfg = stream_config or StreamConfig()
    devices = list(devices)
    T = tasks.n_tasks
    if len(devices) <= 1 or T <= 1:
        return solve_batch_streamed(G, tasks, config, stream_config=cfg,
                                    epoch_fn=epoch_fn,
                                    device=devices[0] if devices else None,
                                    chain_next=chain_next,
                                    return_stats=return_stats)

    if not getattr(G, "is_shard_view", False):
        # Keep a shards.GShardView disk-resident — the shared reader slices
        # row blocks from it like any ndarray.
        G = np.asarray(G, np.float32)
    n, rank = G.shape
    idx = np.asarray(tasks.idx)
    y = np.asarray(tasks.y, np.float32)
    c = np.asarray(tasks.c, np.float32)
    a0 = np.asarray(tasks.alpha0, np.float32)
    row_counts = (c > 0.0).sum(axis=1)
    parts = (balance_chain_split(row_counts, chain_next, len(devices))
             if chain_next is not None
             else balance_task_split(row_counts, len(devices)))
    subs = [TaskBatch(idx[p], y[p], c[p], a0[p]) for p in parts]
    sub_chains = [_local_chain(chain_next, p) for p in parts]

    if not overlap:
        results, per_dev = [], []
        for d, sub, ch in zip(devices, subs, sub_chains):
            r, s = solve_batch_streamed(G, sub, config, stream_config=cfg,
                                        epoch_fn=epoch_fn, device=d,
                                        chain_next=ch, return_stats=True)
            results.append(r)
            per_dev.append(s)
        res = _scatter_results(parts, results, T, idx.shape[1], rank)
        if not return_stats:
            return res
        # Serial aggregate: a zero reader record — every device paid its own
        # G stream, so mesh-level bytes sum to ~D x the single-device figure
        # (exactly the cost the overlapped farm removes).
        from repro.core.solver_stream import Stage2StreamStats
        reader0 = Stage2StreamStats(tile_rows=per_dev[0].tile_rows,
                                    block_dtype=cfg.block_dtype)
        return res, merge_stream_stats(
            reader0, per_dev, seconds=time.perf_counter() - t0,
            n_devices=len(subs))

    epoch_fn = epoch_fn or default_epoch_fn()
    # One int8 scale-table cache for the whole farm: every engine streams
    # the same G, so the global group scales are computed once, not once
    # per device.
    scale_cache: dict = {}
    tr = resolve_tracer(cfg.trace)

    # Fault tolerance: the guard snapshots the GLOBAL-task-keyed solver state
    # at every epoch boundary (in memory when fail_fast=False, to disk every
    # checkpoint_every full passes), so a lost device's shard can be re-split
    # onto the survivors and the farm re-entered from the last boundary.
    guard = None
    if cfg.checkpoint_dir or not cfg.fail_fast:
        from repro.core.resilience import StreamGuard, g_fingerprint
        guard = StreamGuard(cfg, n=n, rank=rank, sizes=row_counts,
                            g_fp=g_fingerprint(G), degrade=not cfg.fail_fast)
        if cfg.checkpoint_dir and cfg.resume:
            snap = guard.try_resume()
            if snap is not None:
                guard.adopt(snap)

    avail = list(devices)
    dev_ids = list(range(len(avail)))   # original indices — names stay
    #   stable across quarantines so per-device fault specs / traces line up
    while True:
        parts = (balance_chain_split(row_counts, chain_next, len(avail))
                 if chain_next is not None
                 else balance_task_split(row_counts, len(avail)))
        subs = [TaskBatch(idx[p], y[p], c[p], a0[p]) for p in parts]
        sub_chains = [_local_chain(chain_next, p) for p in parts]
        # One tile for ALL engines (the shared reader stages each block
        # once); sized by the fattest shard so every in-flight set fits.
        tile = auto_tile_rows(n, rank, max(len(p) for p in parts), cfg)
        names = [f"dev{dev_ids[j]}" for j in range(len(avail))]
        engines = [_Stage2Engine(G, sub, config, cfg, epoch_fn=epoch_fn,
                                 device=d, tile=tile,
                                 scale_cache=scale_cache, chain_next=ch,
                                 name=nm, task_ids=p)
                   for d, sub, ch, nm, p in zip(avail, subs, sub_chains,
                                                names, parts)]
        if guard is not None and guard.mem is not None:
            from repro.core.resilience import restore_engines
            restore_engines(engines, guard.mem)
        workers = _DeviceWorkers(engines, depth=max(2, cfg.prefetch),
                                 trace=cfg.trace, names=names,
                                 watchdog=cfg.watchdog_seconds)
        try:
            reader = drive_streamed_engines(engines, G, config, cfg,
                                            tile=tile, fanout=workers,
                                            guard=guard)
            break
        except Exception:
            failed = workers.failed()
            if (cfg.fail_fast or guard is None or not failed
                    or any(classify_error(e) != "persistent"
                           for e in failed.values())):
                raise
            keep = [j for j in range(len(avail)) if names[j] not in failed]
            if not keep:
                raise
            # Quarantine the lost devices; solver state rolls back to the
            # guard's last epoch-boundary snapshot and the next lap re-splits
            # every task over the survivors (chain-aware LPT, same as a
            # fresh solve at that device count — per-task trajectories are
            # placement-invariant, so the result is bit-equal to a clean
            # run on the surviving devices).
            tr.instant("recovery", "quarantine",
                       lost=len(avail) - len(keep), survivors=len(keep),
                       resume_epoch=int(guard.mem["meta"]["epoch_next"])
                       if guard.mem is not None else 0)
            avail = [avail[j] for j in keep]
            dev_ids = [dev_ids[j] for j in keep]
            guard.adopt_mem()
    pairs = [e.result() for e in engines]
    res = _scatter_results(parts, [p[0] for p in pairs], T, idx.shape[1],
                           rank)
    if not return_stats:
        return res
    return res, merge_stream_stats(
        reader, [p[1] for p in pairs], seconds=time.perf_counter() - t0,
        n_devices=len(engines), carry=guard.carry if guard else None)


def solve_tasks_streamed_mesh(
    mesh: Mesh,
    G,
    tasks: TaskBatch,
    config: SolverConfig,
    *,
    stream_config=None,
    overlap: bool = True,
    return_stats: bool = False,
    chain_next=None,
) -> SolveResult:
    """Out-of-core counterpart of `solve_tasks_sharded` over a mesh's LOCAL
    devices: the row-count-balanced task shards stream G row-blocks
    (core/solver_stream.py), overlapped behind one shared block reader by
    default (`solve_tasks_streamed`)."""
    return solve_tasks_streamed(G, tasks, config,
                                devices=list(mesh.local_devices),
                                stream_config=stream_config, overlap=overlap,
                                chain_next=chain_next,
                                return_stats=return_stats)


# ---------------------------------------------------------------------------
# Stage 1 with explicit shardings (used by launch/dryrun.py and train_svm.py)
# ---------------------------------------------------------------------------

def stage1_gram_sharded(mesh: Mesh, params: KernelParams,
                        row_axes: Sequence[str] = ("data",),
                        col_axis: str = "model"):
    """Return a jit'd K(x, z) with x rows sharded and z columns sharded."""
    row_spec = P(tuple(row_axes), None)
    col_spec = P(col_axis, None)

    @partial(jax.jit,
             in_shardings=(NamedSharding(mesh, row_spec),
                           NamedSharding(mesh, col_spec)),
             out_shardings=NamedSharding(mesh, P(tuple(row_axes), col_axis)))
    def gram_dist(x, z):
        dot = jnp.einsum("np,mp->nm", x, z, precision=jax.lax.Precision.HIGHEST)
        x_sq = jnp.sum(x * x, axis=-1)
        z_sq = jnp.sum(z * z, axis=-1)
        return apply_epilogue(dot, x_sq, z_sq, params)

    return gram_dist


def stage1_project_sharded(mesh: Mesh, row_axes: Sequence[str] = ("data",),
                           col_axis: str = "model"):
    """Return a jit'd (K_nm, projector) -> G with G rows kept data-sharded.

    K_nm arrives (rows x "data", cols x "model"); the projector (B, B') is
    replicated; the contraction over B induces one reduce-scatter/all-reduce
    over "model" — visible in the dry-run collective schedule.
    """
    @partial(jax.jit,
             in_shardings=(NamedSharding(mesh, P(tuple(row_axes), col_axis)),
                           NamedSharding(mesh, P(None, None))),
             out_shardings=NamedSharding(mesh, P(tuple(row_axes), col_axis)))
    def project(k_nm, projector):
        return jnp.einsum("nb,bk->nk", k_nm, projector,
                          precision=jax.lax.Precision.HIGHEST)

    return project


def stage1_project_sharded_v2(mesh: Mesh, row_axes: Sequence[str] = ("data",),
                              col_axis: str = "model"):
    """Beyond-paper §Perf fix for the stage-1 projection (hillclimb #3).

    The baseline keeps K_nm sharded (rows x "data", cols x "model") and lets
    GSPMD handle the contraction over the "model"-sharded budget axis — which
    it implements by ALL-GATHERING the full (n_loc, B) block on every device
    (25 GB/device at the paper's n=10^7, B=10^4 scale; temp 46.6 GiB).

    Hypothesis: resharding K_nm to rows x ("data","model") first makes the
    matmul fully local — the only collective is the reshard itself, which
    moves each element once (1.56 GB/device) instead of (M-1)x.
    """
    all_rows = tuple(row_axes) + (col_axis,)

    @partial(jax.jit,
             in_shardings=(NamedSharding(mesh, P(tuple(row_axes), col_axis)),
                           NamedSharding(mesh, P(None, None))),
             out_shardings=NamedSharding(mesh, P(all_rows, None)))
    def project(k_nm, projector):
        k_nm = jax.lax.with_sharding_constraint(k_nm, P(all_rows, None))
        return jnp.einsum("nb,bk->nk", k_nm, projector,
                          precision=jax.lax.Precision.HIGHEST)

    return project


def replicate(mesh: Mesh, x):
    return jax.device_put(x, NamedSharding(mesh, P(*((None,) * x.ndim))))


# ---------------------------------------------------------------------------
# Stage 1 out-of-core over a mesh: disjoint row-chunk streams per device
# ---------------------------------------------------------------------------

def stream_factor_over_mesh(
    mesh: Mesh,
    x,
    landmarks,
    projector,
    params: KernelParams,
    *,
    chunk_rows: int,
    prefetch: int = 2,
    gram_fn=None,
    out=None,
):
    """Chunked stage-1 G over every device of `mesh` (host-resident x and G).

    The complement of `stage1_gram_sharded`: that path assumes the full
    (n, p) x and (n, B) K_nm fit *sharded across* the mesh; this one assumes
    they only fit in host RAM.  Row chunks are handed round-robin to the
    flattened mesh devices, so each device owns a disjoint chunk stream with
    its own resident landmark/projector replica and its own double-buffered
    H2D/compute/D2H overlap — no collectives at all in stage 1, matching the
    paper's embarrassingly-row-parallel gram computation.  The replicated
    stage-2 task farm (`solve_tasks_sharded`) consumes the resulting G
    unchanged.
    """
    from repro.core.kernel_fn import gram as _gram_ref
    from repro.core.streaming import stream_factor_rows

    # Only this process's devices: device_put to another host's chip raises.
    # Multi-host meshes stream their own row range per host (ROADMAP item).
    devices = list(mesh.local_devices)
    return stream_factor_rows(
        x, landmarks, projector, params, chunk_rows=chunk_rows,
        prefetch=prefetch, gram_fn=gram_fn or _gram_ref, out=out,
        devices=devices)


def compute_factor_streamed_mesh(
    mesh: Mesh,
    x,
    params: KernelParams,
    budget: int,
    *,
    key=None,
    stream_config=None,
    gram_fn=None,
):
    """`streaming.compute_factor_streamed` with the chunk streams spread over
    `mesh` — the full two-stage entry point for a multi-device host."""
    from repro.core.kernel_fn import gram as _gram_ref
    from repro.core.streaming import StreamConfig, compute_factor_streamed

    devices = list(mesh.local_devices)
    return compute_factor_streamed(
        x, params, budget, key=key, config=stream_config or StreamConfig(),
        gram_fn=gram_fn or _gram_ref, devices=devices)
