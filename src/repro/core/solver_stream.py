"""Out-of-core stage 2: stream G row-blocks through the SMO epoch.

The paper keeps alpha on the GPU and the full factor G in host RAM ("more
RAM!"), so the trainable n is bounded by the 512 GB-class host, not device
HBM.  `dual_solver.solve_batch` re-materialises all of G on device when it
traces, silently re-capping n at HBM; this module closes that gap:

    host RAM                              device HBM
    ───────────────────────────────       ────────────────────────────────
    G        (n, B)   read-only           w        (T, B)   resident, chained
    alpha    (T, n)   scattered back      per block: G[s:e], y/c/q/alpha/
    unchanged(T, n)   per block                      unchanged slices

Per epoch, (tile, B) row-blocks of G are `device_put` with the same
prefetch-deep async double buffering as `core/streaming.py` (enqueue block
k+1's H2D + kernel launches before draining block k's alpha back to host),
and every streamed block updates EVERY task before eviction, so the H2D
traffic is amortised over the whole OVO/CV task batch.  The per-task weight
vector w stays device-resident across blocks and epochs — the cross-block
analogue of the SMO kernel's VMEM scratchpad (kernels/smo.py).

The per-epoch block pass lives in `_Stage2Engine`, a per-(device, task-shard)
state machine: a driver (`drive_streamed_engines`) owns the lockstep epoch
schedule, reads each (tile, B) block of G ONCE per shared pass
(`iter_shared_blocks`) and fans it out to every live engine, while compacted
cheap epochs run engine-locally over each shard's own active-row union.
`solve_batch_streamed` is the one-engine instantiation; the overlapped
multi-device task farm (`core/distributed.py::solve_tasks_streamed`) drives
many engines behind per-device host workers so H2D, compute, and D2H overlap
ACROSS devices and the host-resident G is streamed once per pass instead of
once per device.  Blocks can optionally cross the bus as bfloat16
(`StreamConfig.block_dtype="bf16"`, upcast on device) for half the stage-2
H2D bytes, or as int8 with per-row-group scale/zero tables
(`block_dtype="int8"`, the `core/quant.py` codec, dequantised fused on
device) for a quarter of them; `tune_prefetch` closes a minimal
overlap-autotune loop: when the first full pass measures H2D time exceeding
the compute/drain time it is meant to hide, the in-flight queue is deepened.

Shrinking follows `core/compact.py`'s bucket-compaction design, but here it
cuts H2D *bytes*, not just FLOPs: after every full pass the union of active
rows over all unconverged tasks is gathered host-side, and the cheap epochs
stream only those rows.

Task state is held in task-LOCAL streamed coordinates: per task, the sorted
real (c > 0) global row ids plus (y, c, alpha, unchanged) vectors of that
length, so host memory is O(sum task sizes) — not the O(T * n) a
global-coordinate scatter would cost (a ~k/2 blowup for OVO, and a
T/pairs-fold one for the CV-grid task farm where T = pairs x folds x |Cs|).
Each streamed block touches a task through a `searchsorted` WINDOW: a
precomputed per-task boundary table maps block b to the contiguous id slice
lo:hi whose rows fall inside the block, the (hi - lo) block-local rows are
gathered on device, and the epoch kernel sweeps only them — kernel work is
O(sum task sizes) per pass too (`Stage2StreamStats.coord_visits`).  Sweeping
a task's rows in sorted-global order is exactly what the inert-padded global
sweep did, so the streamed trajectory still reproduces the monolithic
`solve_one` trajectory to float accumulation order, including shrinking
counters and warm starts.

Requirements on the TaskBatch: each task's real (c > 0) rows must be unique;
sorted idx (what `build_ovo_tasks`/`build_cv_tasks` produce) additionally
gives trajectory-exact parity with the monolithic path (unsorted idx is
re-sorted internally — the sweep is global-row-ordered either way).

The task axis can also carry a C-LADDER: `chain_next[t] = s` declares task s
the warm-start successor of task t over the same rows (the CV grid's next-C
cell, `cv.build_cv_grid_tasks`).  Successor cells start dormant; when a
predecessor converges at a full pass its alphas are clipped into the new box
as the successor's seed, the successor's w0 accumulation rides the next
shared full pass (the driver promotes it), and the retired cell stops
consuming kernel calls — one G stream trains the whole grid.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import math
import time
from functools import partial
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from repro.core.block_cache import (HotRowBlockCache, block_key,
                                    stage2_cache_budget,
                                    violation_recency_scores_tasks)
from repro.core.dual_solver import (DELTA_EPS, Q_FLOOR, SolveResult,
                                    SolverConfig, TaskBatch)
from repro.core.faults import check as _fault_check
from repro.core.faults import classify_error
from repro.core.kernel_fn import HIGHEST
from repro.core.quant import (GROUP_ROWS, QuantBlock, dequant_rows,
                              encode_rows, group_scales, quantize_block)
from repro.core.streaming import BYTES_F32, StreamConfig, tune_prefetch
from repro.core.trace import resolve as resolve_tracer

_H2D_GUARD = getattr(jax, "transfer_guard_host_to_device", None)


# ---------------------------------------------------------------------------
# stage-2 memory budget model (documented in docs/architecture.md)
# ---------------------------------------------------------------------------

def stage2_resident_bytes(rank: int, n_tasks: int) -> int:
    """Device-resident stage-2 state: one (B,) weight vector per task."""
    return n_tasks * rank * BYTES_F32


def stage2_block_bytes(tile: int, rank: int, n_tasks: int) -> int:
    """Working set of ONE in-flight block: the G tile plus, per task, the
    five input vectors (y, c, q, alpha, unchanged) and two outputs."""
    return tile * (rank + 7 * n_tasks) * BYTES_F32


def stage2_monolithic_bytes(n: int, rank: int, n_tasks: int, n_pad: int) -> int:
    """Device working set of `solve_batch`: full G + per-task vectors."""
    return (n * rank + n_tasks * (7 * n_pad + 2 * rank)) * BYTES_F32


def should_stream_stage2(n: int, rank: int, n_tasks: int, n_pad: int,
                         cfg: StreamConfig) -> bool:
    """True when the monolithic stage-2 working set blows the device budget."""
    return stage2_monolithic_bytes(n, rank, n_tasks, n_pad) > cfg.device_budget_bytes


def route_stage2(factor, tasks: TaskBatch, stream,
                 stream_config: Optional[StreamConfig],
                 solve_fn, default_solve_fn) -> bool:
    """The ONE stage-2 routing predicate (`LPDSVM.fit`, `core/cv.py`, CLI):
    stream G row-blocks when G is already host-resident (`factor.streamed`),
    streaming is forced, or the monolithic working set exceeds the device
    budget.  A custom ``solve_fn`` (e.g. the sharded task farm) is always
    respected, and ``stream=False`` pins the monolithic path.
    """
    if solve_fn is not default_solve_fn or stream is False:
        return False
    if stream or getattr(factor, "streamed", False):
        return True
    if stream_config is None:
        return False
    n, rank = factor.G.shape
    return should_stream_stage2(n, rank, tasks.n_tasks, tasks.idx.shape[1],
                                stream_config)


def auto_tile_rows(n: int, rank: int, n_tasks: int, cfg: StreamConfig) -> int:
    """Largest row tile whose `prefetch` in-flight blocks fit the budget.

    Solves  prefetch * stage2_block_bytes(t) + resident <= budget  for t,
    floored at `min_chunk_rows` (tiny budgets should not degenerate into
    per-row dispatch) and rounded up to a multiple of 8.  An EXPLICIT
    `cache_budget_bytes` is carved out of the free bytes first — that HBM is
    promised to the hot-row block cache; the default derived cache budget is
    *defined* as whatever this model leaves over (`stage2_cache_budget`), so
    it never shrinks the tile.
    """
    if cfg.tile_rows is not None:
        return max(8, -(-min(cfg.tile_rows, n) // 8) * 8)
    free = cfg.device_budget_bytes - stage2_resident_bytes(rank, n_tasks)
    if cfg.cache_blocks and cfg.cache_budget_bytes:
        free -= cfg.cache_budget_bytes
    per_row = cfg.prefetch * (rank + 7 * n_tasks) * BYTES_F32
    rows = (free // per_row) // 8 * 8 if free > 0 else 0   # round down: budget
    return int(min(-(-n // 8) * 8, max(cfg.min_chunk_rows, rows, 8)))


# ---------------------------------------------------------------------------
# block-epoch kernels
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("full_pass", "shrink_k"))
def smo_epoch_oracle(G, y, c, q, alpha, unchanged, w, *, full_pass: bool,
                     shrink_k: int):
    """One sequential coordinate-ascent sweep over a (tile, B) block.

    Flat 1-D vectors in/out, same contract as `kernels.ops.smo_epoch`; the
    body mirrors `dual_solver.epoch_ref` op-for-op so that chaining blocks
    reproduces the monolithic trajectory exactly.
    """
    n = G.shape[0]

    def body(i, state):
        alpha, w, unchanged, viol = state
        row = G[i]
        a_i, c_i, y_i, q_i = alpha[i], c[i], y[i], q[i]
        active = jnp.logical_and(
            c_i > 0.0, jnp.logical_or(full_pass, unchanged[i] < shrink_k))
        g = 1.0 - y_i * jnp.dot(w, row, precision=HIGHEST)
        at_lo = a_i <= 0.0
        at_hi = a_i >= c_i
        pg = jnp.where(at_lo, jnp.maximum(g, 0.0),
                       jnp.where(at_hi, jnp.minimum(g, 0.0), g))
        pg = jnp.where(c_i > 0.0, pg, 0.0)
        a_new = jnp.clip(a_i + g / jnp.maximum(q_i, Q_FLOOR), 0.0, c_i)
        a_new = jnp.where(active, a_new, a_i)
        delta = a_new - a_i
        w = w + (delta * y_i) * row
        alpha = alpha.at[i].set(a_new)
        changed = jnp.abs(delta) > DELTA_EPS
        u_new = jnp.where(changed, 0, unchanged[i] + 1)
        u_new = jnp.where(active, u_new, unchanged[i])
        unchanged = unchanged.at[i].set(u_new)
        viol = jnp.where(active, jnp.maximum(viol, jnp.abs(pg)), viol)
        return alpha, w, unchanged, viol

    alpha, w, unchanged, viol = jax.lax.fori_loop(
        0, n, body, (alpha, w, unchanged, jnp.float32(0.0)))
    return alpha, unchanged, w, viol


def default_epoch_fn() -> Callable:
    """Pallas SMO kernel on TPU; the jnp oracle elsewhere (interpret-mode
    Pallas is pure overhead on CPU, and the oracle matches `epoch_ref`)."""
    if jax.default_backend() == "tpu":
        from repro.kernels.ops import smo_epoch
        return smo_epoch
    return smo_epoch_oracle


@jax.jit
def _row_sq(G):
    """Per-row squared norms — same op as `solve_one`'s q computation.

    Recomputed on device from the streamed block every pass: q is a pure
    function of the block's bytes, so this is bit-identical to caching it on
    host while saving the q H2D/D2H round trips entirely.
    """
    return jnp.sum(G ** 2, axis=-1)


@jax.jit
def _accum_w(w, G, alpha, y):
    """Warm-start w accumulation: w += (alpha * y) @ G_block."""
    return w + jnp.dot(alpha * y, G, precision=HIGHEST)


@jax.jit
def _upcast32(g):
    """Device-side upcast of a bf16 wire block back to the fp32 the epoch
    kernels accumulate in (the H2D copy moved half the bytes)."""
    return g.astype(jnp.float32)


@jax.jit
def _window(gb, qb, rl):
    """Device gather of one task's window out of a streamed block: the
    (win,) block-local row ids ``rl`` select the task's rows (and their
    precomputed q) so the epoch kernel sweeps only them."""
    return gb[rl], qb[rl]


@jax.jit
def _gather_rows(gb, rl):
    return gb[rl]


def _win_pad(m: int) -> int:
    """Pow2-bucketed device window length (floor 8): window kernels compile
    once per bucket instead of once per ragged window size; pad rows carry
    c = 0 and are inert in the epoch kernel."""
    return max(8, 1 << (int(m) - 1).bit_length())


def block_windows(ids: np.ndarray, tile: int, n_blocks: int) -> np.ndarray:
    """Boundary table of a task's SORTED global row ids against the block
    grid: entry b is the first position in ``ids`` at or past row b * tile,
    so block b's window is the contiguous slice bounds[b]:bounds[b+1] and
    its block-local rows are ids[lo:hi] - b * tile.  One O(m log m)
    searchsorted per task at engine build; O(1) per (task, block) after —
    the mapping that makes host state and kernel work O(sum task sizes)."""
    edges = np.arange(n_blocks + 1, dtype=np.int64) * tile
    return np.searchsorted(np.asarray(ids, np.int64), edges, side="left")


def _put(a, device=None):
    """Deliberate H2D transfer of one bounded block.

    Kept as the single host->device choke point: tests run the whole solve
    under `jax.transfer_guard_host_to_device("disallow")` to prove the full
    G is never device-materialised; only these explicit block puts are
    allowed through.
    """
    cm = (_H2D_GUARD("allow") if _H2D_GUARD is not None
          else contextlib.nullcontext())
    with cm:
        return jax.device_put(a) if device is None else jax.device_put(a, device)


# ---------------------------------------------------------------------------
# the streamed batch solver: stats, block reader, per-device engine, driver
# ---------------------------------------------------------------------------

BLOCK_DTYPES = {"f32": np.float32, "bf16": ml_dtypes.bfloat16,
                "int8": np.int8}


def wire_group(tile: int, cfg: StreamConfig) -> int:
    """Effective int8 scale-group rows for a given block tile.

    Group boundaries must ALIGN with block boundaries so that a row's
    encoding is the same whether it travels in a shared full-pass block or a
    compacted cheap-epoch block (group stats are global-row-aligned either
    way); `auto_tile_rows` makes every tile a multiple of 8, so
    gcd(tile, requested) is at least 8 for the default group of 32 — the
    scale overhead stays at 8 bytes per >= 8 rows."""
    return math.gcd(tile, max(1, cfg.quant_group_rows))


@dataclasses.dataclass
class Stage2StreamStats:
    """Traffic + convergence accounting of one streamed stage-2 solve.

    On a multi-device farm this is the MESH-level record.  Two H2D views:

    * `bytes_h2d` — UNIQUE bytes read out of the host-resident G (plus the
      partitioned per-task vector traffic).  Shared-pass G blocks count
      once no matter how many devices consume them: the host-RAM read and
      staging (pad/cast) happen once, which is what the shared reader
      dedupes — so per-pass `bytes_h2d` is independent of device count.
    * `bytes_put` — PHYSICAL per-device DMA bytes issued (each device still
      copies every broadcast block into its own memory, so the G component
      scales with device count; on real hardware those copies ride
      parallel per-device DMA engines).  Size bus bandwidth from this one.

    The unmerged per-device views live in `per_device`.
    """

    tile_rows: int = 0
    epochs: int = 0
    full_passes: int = 0
    rows_streamed: int = 0            # sum of block rows over all epochs/passes
    blocks_streamed: int = 0
    kernel_calls: int = 0
    coord_visits: int = 0             # real task-rows swept by epoch kernels
                                      # (the windowed analogue of the
                                      # monolithic epochs.sum() * task size)
    bytes_h2d: int = 0
    bytes_d2h: int = 0
    bytes_g: int = 0                  # G-block component of bytes_h2d alone
                                      # (shared-pass stages + compacted-epoch
                                      # misses; excludes per-task vectors) —
                                      # the figure the grid farm's "one pass
                                      # set per grid" claim is asserted on
    bytes_scales: int = 0             # int8 codec scale-table bytes (already
                                      # included in bytes_h2d / bytes_put —
                                      # broken out so the exact-byte
                                      # invariants stay assertable)
    epoch_bytes: List[int] = dataclasses.field(default_factory=list)
    active_history: List[int] = dataclasses.field(default_factory=list)
    # ^ per compaction: active-row union size (single device) / total rows
    #   streamed per cheap epoch across shards (mesh — unions may overlap)
    # HBM block-cache accounting.  Every compacted cheap-epoch G block lands
    # in exactly ONE of hit/miss: `bytes_miss` is what crossed the bus
    # (already inside `bytes_h2d`), `bytes_hit` is what the pinned union
    # served device-side instead.  With caching off every compacted block is
    # a miss, so cached.bytes_hit + cached.bytes_miss == uncached.bytes_miss
    # and cached.bytes_h2d == uncached.bytes_h2d - cached.bytes_hit — the
    # exact identities tests/test_block_cache.py asserts.
    bytes_hit: int = 0                # cache-served G bytes (zero H2D)
    bytes_miss: int = 0               # compacted cheap-epoch G bytes shipped
    cache_hits: int = 0               # block-granular counters of the same
    cache_misses: int = 0
    cache_evictions: int = 0
    cache_resident_bytes: int = 0     # peak pinned HBM bytes (sum over
                                      # devices on a farm)
    epoch_hit_bytes: List[int] = dataclasses.field(default_factory=list)
    epoch_miss_bytes: List[int] = dataclasses.field(default_factory=list)
    # ^ per-epoch hit/miss deltas, index-aligned with `epoch_bytes`, so
    #   benchmarks plot byte decay vs hit-rate without re-deriving it
    seconds: float = 0.0
    block_dtype: str = "f32"
    n_devices: int = 1
    bytes_put: int = 0                # physical per-device DMA bytes
    put_seconds: float = 0.0          # host time inside H2D puts
    drain_seconds: float = 0.0        # host time blocked on result fetches
    prefetch_final: int = 0           # queue depth after autotune
    per_device: Optional[List["Stage2StreamStats"]] = None

    @property
    def epoch_hit_rate(self) -> List[float]:
        """Per-epoch cache-hit fraction of compacted G bytes (0.0 for epochs
        with no compacted traffic, e.g. full passes)."""
        return [h / (h + m) if h + m else 0.0
                for h, m in zip(self.epoch_hit_bytes, self.epoch_miss_bytes)]

    @property
    def h2d_gbps(self) -> float:
        """Effective H2D rate over host put time (GB/s), on the PHYSICAL
        per-device DMA bytes (`bytes_put`) — put time is spent issuing every
        copy, broadcast or not."""
        return self.bytes_put / max(self.put_seconds, 1e-12) / 1e9

    @property
    def overlap_efficiency(self) -> float:
        """Stall-free fraction of the wall clock: 1 minus the share spent
        blocked in puts/drains, clamped to [0, 1].  The trace-level
        `Tracer.overlap_efficiency` is the per-span timeline analogue."""
        if self.seconds <= 0.0:
            return 0.0
        busy = (self.put_seconds + self.drain_seconds) / self.seconds
        return min(1.0, max(0.0, 1.0 - busy))


class _PadStage:
    """One reusable padded staging buffer for ragged tail blocks.

    `prep_block` used to `np.zeros((tile, B))` for EVERY ragged tail — once
    per pass per solve, and once per cheap epoch per engine.  A stage owns
    that buffer instead: allocated once, zero-tail-refreshed per use.  Reuse
    is safe wherever the previous occupant has been consumed before the next
    `pad` call: `jax.device_put` copies the host buffer before returning, so
    an engine's own sequential block loop may always reuse, and the shared
    reader may reuse ACROSS passes because the driver barriers every pass
    (all queued fan-out closures have run).  The int8 wire pads its encoded
    values through the same buffer (an int8 one) under the same rules.
    """

    def __init__(self, tile: int, rank: int, block_dtype: str):
        # int8 tails are padded AFTER encoding (zero codes + inert scale
        # entries), so the staging buffer holds the wire dtype either way.
        self.buf = np.zeros((tile, rank), BLOCK_DTYPES[block_dtype])

    def pad(self, gb: np.ndarray) -> np.ndarray:
        cnt = gb.shape[0]
        self.buf[:cnt] = gb
        self.buf[cnt:] = 0
        return self.buf


def pad_quant_block(qb: QuantBlock, tile: int,
                    stage: Optional[_PadStage] = None) -> QuantBlock:
    """Pad a quantised block to ``tile`` rows: zero codes for the pad rows
    and inert (scale 1, zero 0) entries for all-pad scale groups, so pads in
    a FULL pad group dequantise to exact zeros; pads sharing a ragged real
    group decode to that group's zero-point — harmless, the epoch kernel
    treats their c = 0 rows as inert."""
    cnt, ng = qb.values.shape[0], qb.scales.shape[0]
    ng_pad = -(-tile // qb.group)
    if stage is not None:
        values = stage.pad(qb.values)
    else:
        values = np.zeros((tile, qb.values.shape[1]), np.int8)
        values[:cnt] = qb.values
    scales = np.zeros((ng_pad, 2), np.float32)
    scales[:ng] = qb.scales
    scales[ng:, 0] = 1.0
    return QuantBlock(values=values, scales=scales, group=qb.group)


def prep_block(gb: np.ndarray, tile: int, block_dtype: str,
               group: int = GROUP_ROWS, stage: Optional[_PadStage] = None):
    """Pad a host G row-block to ``tile`` rows and encode it in the wire
    format: an f32/bf16 ndarray, or a `QuantBlock` (int8 values + per-row-
    group f32 scale/zero table) for ``block_dtype="int8"``.

    Full-tile f32/bf16 blocks already in the wire dtype pass through as views
    of an (immutable) host buffer — G itself, or an engine's wire-dtype
    `act_G` gather; a block that needs padding or casting gets a buffer from
    ``stage`` (reusable, see `_PadStage`) or a fresh one.  int8 blocks are
    quantised from the REAL rows only and padded after encoding
    (`pad_quant_block`) — with ``group`` dividing ``tile`` (see `wire_group`)
    the group stats equal the global-row-aligned stats, so a row's code is
    block-shape-independent and the shrinking-compacted cheap epochs re-emit
    the same decoded values (to FMA rounding).
    """
    if block_dtype == "int8":
        qb = quantize_block(np.asarray(gb, np.float32), group)
        return qb if gb.shape[0] == tile else pad_quant_block(qb, tile, stage)
    if gb.shape[0] == tile and gb.dtype == BLOCK_DTYPES[block_dtype]:
        return gb
    if gb.shape[0] != tile and stage is not None:
        # Only the ONE ragged tail per pass may use the shared stage buffer:
        # full-tile casts (bf16) must stay fresh — several sit in per-device
        # queues at once.
        return stage.pad(gb)
    buf = np.zeros((tile, gb.shape[1]), BLOCK_DTYPES[block_dtype])
    buf[: gb.shape[0]] = gb
    return buf


def iter_shared_blocks(G: np.ndarray, tile: int, block_dtype: str,
                       group: int = GROUP_ROWS,
                       stage: Optional[_PadStage] = None, trace=None):
    """The shared host block reader: yield each (tile, B) row-block of G
    exactly once as ``(sel, cnt, gb_send)`` — the driver fans every yielded
    buffer out to all live engines, so a full pass reads G (and, for the
    int8 wire, quantises it) once regardless of device count.  ``stage`` is
    the caller-owned reusable pad buffer; the driver allocates it once per
    solve and its per-pass barrier makes cross-pass reuse safe.  ``trace``
    records one ``read`` span per staged block (the host-RAM read + pad /
    encode work the reader dedupes across devices)."""
    n = G.shape[0]
    tr = resolve_tracer(trace)
    for b in range(math.ceil(n / tile)):
        s, e = b * tile, min((b + 1) * tile, n)
        t0 = tr.begin()
        try:
            _fault_check("reader", block=b)
            gb_send = prep_block(G[s:e], tile, block_dtype, group, stage)
        except BaseException as exc:
            # Close the in-flight span before propagating so a failed run
            # still exports a valid, complete trace timeline.
            tr.end("read", "stage_block", t0, rows=e - s, block=b,
                   error=type(exc).__name__)
            tr.instant("fault", "reader_error", block=b,
                       error=type(exc).__name__)
            raise
        tr.end("read", "stage_block", t0, bytes=int(gb_send.nbytes),
               rows=e - s, block=b)
        yield slice(s, e), e - s, gb_send


class _BlockPipeline:
    """The prefetch-deep in-flight queue (async double buffer, cf.
    `streaming.stream_factor_rows`): results are only fetched to host when
    the queue is full or the pass ends, so H2D, compute, and D2H overlap.
    ``prefetch`` is mutable — the overlap-autotune loop deepens it when the
    first full pass measures transfer lagging compute."""

    def __init__(self, prefetch: int, a_r, u_r, stats, trace=None):
        self.inflight = collections.deque()
        self.prefetch = max(1, prefetch)
        self.a_r, self.u_r = a_r, u_r
        self.stats = stats
        self.trace = resolve_tracer(trace)

    def push(self, items):
        if not items:
            return
        self.inflight.append(items)
        if len(self.inflight) >= self.prefetch:
            self._drain_one()

    def flush(self):
        while self.inflight:
            self._drain_one()

    def _drain_one(self):
        items = self.inflight.popleft()
        t0 = self.trace.begin()
        nb = 0
        for t, take, m, a_ref, u_ref in items:
            # ``take`` addresses the window in the task-LOCAL arrays: a
            # contiguous slice on full passes, an active-position gather on
            # compacted cheap epochs.
            self.a_r[t][take] = np.asarray(a_ref)[:m]
            self.u_r[t][take] = np.asarray(u_ref)[:m]
            self.stats.bytes_d2h += 2 * m * BYTES_F32
            nb += 2 * m * BYTES_F32
        self.stats.drain_seconds += self.trace.end(
            "drain", "block_drain", t0, bytes=nb, windows=len(items))


def _padded(vec, fill, dtype, tile):
    if vec.shape[0] == tile:
        return np.ascontiguousarray(vec, dtype)
    buf = np.full((tile,), fill, dtype)
    buf[: vec.shape[0]] = vec
    return buf


class _Stage2Engine:
    """One device's streamed stage-2 state machine — the reusable per-epoch
    block pass (window selection, q computation, SMO step, pipeline drain,
    shrinking compaction) parameterised by (device, task shard, w state).

    The engine owns its shard's host-side TASK-LOCAL coordinate state
    (sorted real row ids + y/c/alpha/unchanged of each task's own length —
    O(sum task sizes), never O(T * n)), the per-task `searchsorted` window
    tables against the block grid, the device-resident per-task w vectors,
    and the in-flight block pipeline.  A driver (`drive_streamed_engines`)
    owns the lockstep epoch schedule and feeds shared full-G passes block by
    block; compacted cheap epochs run engine-locally (`run_cheap_epoch`)
    over the shard's own active-row union.  Engines never count shared-pass
    G bytes — the reader stages each block once and accounts for it once —
    only their task-vector traffic and their own compacted-epoch gathers.

    ``chain_next`` lifts the task axis to warm-start LADDERS (the CV grid's
    ascending-C cells): successor tasks start dormant, are seeded from their
    converged predecessor's alphas, accumulate w0 during the next shared
    full pass (`pending_init`), and only then join the live sweep.
    """

    def __init__(self, G, tasks: TaskBatch, config: SolverConfig,
                 cfg: StreamConfig, *, epoch_fn: Callable, device, tile: int,
                 scale_cache: Optional[dict] = None, chain_next=None,
                 name: str = "dev0", task_ids=None):
        self.G = G
        self.config, self.cfg = config, cfg
        self.epoch_fn, self.device, self.tile = epoch_fn, device, tile
        self.name = name
        # Global task indices of this shard — the key space snapshots are
        # written in, so a checkpoint restores onto ANY device split.
        self.task_ids = (np.arange(tasks.n_tasks, dtype=np.int64)
                         if task_ids is None
                         else np.asarray(task_ids, np.int64))
        # Transient-H2D retry policy: 0 retries under fail_fast (the default
        # pre-PR semantics — a put either succeeds or raises immediately).
        self._retries = 0 if cfg.fail_fast else cfg.max_retries
        self._backoff = cfg.retry_backoff
        n, rank = G.shape
        self.n, self.rank = n, rank
        self.idx = np.asarray(tasks.idx)
        self.y_loc = np.asarray(tasks.y, np.float32)
        self.c_loc = np.asarray(tasks.c, np.float32)
        self.a0_loc = np.asarray(tasks.alpha0, np.float32)
        self.T, self.n_pad = self.idx.shape
        T = self.T

        # Task-LOCAL streamed coordinates: per task, the globally sorted
        # real (c > 0) rows and their solver state, plus the full-pass
        # window boundary table against the block grid.  `scat` remembers
        # each sorted row's position in the task's original padded layout
        # for the result scatter.
        self.real_loc = self.c_loc > 0.0
        self.n_blocks = math.ceil(n / tile)
        self.ids: List[np.ndarray] = []
        self.scat: List[np.ndarray] = []
        self.y_r: List[np.ndarray] = []
        self.c_r: List[np.ndarray] = []
        self.a_r: List[np.ndarray] = []
        self.u_r: List[np.ndarray] = []
        self.bounds: List[np.ndarray] = []
        for t in range(T):
            pos = np.where(self.real_loc[t])[0]
            ids = self.idx[t][pos].astype(np.int64)
            order = np.argsort(ids, kind="stable")
            ids, pos = ids[order], pos[order]
            self.ids.append(ids)
            self.scat.append(pos)
            self.y_r.append(np.ascontiguousarray(self.y_loc[t][pos]))
            self.c_r.append(np.ascontiguousarray(self.c_loc[t][pos]))
            self.a_r.append(np.clip(self.a0_loc[t][pos], 0.0, self.c_r[t]))
            self.u_r.append(np.zeros(len(ids), np.int32))
            self.bounds.append(block_windows(ids, tile, self.n_blocks))

        # C-ladder lifecycle: cold roots sweep from epoch 0; warm roots ride
        # the init pass first (pending); successor cells wait for their
        # predecessor's converged alphas.  `active` means "has its w0 and is
        # sweeping"; `first_sweep` anchors per-task LOCAL epoch counting so
        # `epochs_used` matches what a standalone solve of the cell reports.
        self.chain_next = (np.full((T,), -1, np.int64) if chain_next is None
                           else np.asarray(chain_next, np.int64))
        succ = {int(s) for s in self.chain_next if s >= 0}
        root = [t not in succ for t in range(T)]
        self.pending_init: List[int] = [t for t in range(T)
                                        if root[t] and self.a_r[t].any()]
        pend = set(self.pending_init)
        self.active = np.array([root[t] and t not in pend
                                for t in range(T)], bool)
        self.first_sweep = np.zeros((T,), np.int32)

        self.stats = Stage2StreamStats(tile_rows=tile,
                                       block_dtype=cfg.block_dtype)
        self.trace = resolve_tracer(cfg.trace)
        self.w = [_put(np.zeros((rank,), np.float32), device)
                  for _ in range(T)]
        self.pipe = _BlockPipeline(cfg.prefetch, self.a_r, self.u_r,
                                   self.stats, trace=self.trace)
        self.done = np.zeros((T,), bool)
        self.violation = np.full((T,), np.inf, np.float32)
        self.epochs_used = np.full((T,), config.max_epochs, np.int32)
        self.epochs_run = 0
        self.act: Optional[np.ndarray] = None    # compacted active-row union
        self.act_G: Optional[np.ndarray] = None  # host gather of G[act]
        self.act_q: Optional[List[QuantBlock]] = None
        # ^ int8 wire: per-tile-block quantised shadow of the gather (encoded
        #   once per compaction, reused by every cheap epoch until the next)
        self._cw: dict = {}
        # ^ per-compaction task windows: t -> (take, pos, bounds) where
        #   ``take`` indexes the task-local arrays at its ACTIVE rows,
        #   ``pos`` their sorted positions in the union, and ``bounds`` the
        #   searchsorted block table over pos (compacted analogue of
        #   `self.bounds`); restricting a task to its compaction-time active
        #   rows is trajectory-identical to sweeping them as kernel no-ops
        self.shrink_k = config.shrink_k if config.shrink else 1 << 30
        self._bf16 = cfg.block_dtype == "bf16"
        self._wire = cfg.block_dtype
        self._group = wire_group(tile, cfg)
        self._scale_cache = scale_cache if scale_cache is not None else {}
        # ^ lazy global-row-aligned (ng, 2) scale table of G — computed at
        #   the first compaction and SHARED across a farm's engines (they
        #   stream the same G; a concurrent double-compute is a benign race,
        #   both threads derive the identical table) so compacted rows
        #   re-encode with the exact scales their shared-pass blocks used
        self._stage = _PadStage(tile, rank, cfg.block_dtype)
        # ^ engine-local reusable pad buffer for compacted cheap epochs (the
        #   engine's block loop is sequential, so reuse is safe)
        self.cache = (HotRowBlockCache(
            stage2_cache_budget(rank, T, tile, cfg.prefetch, cfg))
            if cfg.cache_blocks else None)
        # ^ per-engine (hence per-device on a farm) HBM block cache over the
        #   compacted active-row union; shared passes never touch it, so the
        #   device-count-independent shared-reader byte invariant survives
        self._act_keys: Optional[List[bytes]] = None
        self._act_sizes: Optional[List[int]] = None
        self._hit_mark = self._miss_mark = 0
        self._epoch = -1
        self._epoch_mark = 0
        self._put_mark = self._drain_mark = 0.0
        self._kind = None
        self._live: List[int] = []
        self._init_live: List[int] = []
        self._viol = {}

    @property
    def host_state_bytes(self) -> int:
        """Host coordinate-state footprint: the O(sum task sizes) local
        arrays plus the O(T * n / tile) window boundary tables — the memory
        model the grid farm's T >> pairs regime depends on (asserted by the
        memory-model test: no O(T * n) allocation)."""
        per_task = sum(a.nbytes for arrs in (self.ids, self.scat, self.y_r,
                                             self.c_r, self.a_r, self.u_r)
                       for a in arrs)
        return per_task + sum(b.nbytes for b in self.bounds)

    # ------------------------------------------------------------ scheduling
    @property
    def needs_init(self) -> bool:
        """Warm starts need w0 = (alpha0 * y) @ G before the first update."""
        return bool(self.pending_init)

    @property
    def wants_full(self) -> bool:
        """True while freshly seeded ladder successors wait for their w0
        accumulation: it needs FULL row coverage, so the driver promotes the
        next epoch to a shared full pass (the init windows ride the same
        staged blocks — zero extra G traffic)."""
        return bool(self.pending_init)

    @property
    def all_done(self) -> bool:
        return bool(self.done.all())

    def start_epoch(self, epoch: int) -> None:
        self._epoch = epoch
        self._epoch_mark = self.stats.bytes_h2d
        self._hit_mark = self.stats.bytes_hit
        self._miss_mark = self.stats.bytes_miss

    def finish_epoch(self, epoch: int) -> None:
        self.epochs_run = epoch + 1
        self.stats.epoch_bytes.append(self.stats.bytes_h2d - self._epoch_mark)
        self.stats.epoch_hit_bytes.append(self.stats.bytes_hit
                                          - self._hit_mark)
        self.stats.epoch_miss_bytes.append(self.stats.bytes_miss
                                           - self._miss_mark)

    def autotune(self, cap: int) -> None:
        """Close the overlap loop from the FIRST full pass's measured rates:
        deepen the in-flight queue when transfer lagged compute.  The byte
        model still binds: the tuned depth may not push the in-flight device
        working set past `device_budget_bytes` (a deeper queue only helps
        when there is memory to hold it), so `cap` is tightened to the
        largest depth that fits before `tune_prefetch` runs."""
        free = (self.cfg.device_budget_bytes
                - stage2_resident_bytes(self.rank, self.T))
        per_block = stage2_block_bytes(self.tile, self.rank, self.T)
        fit = free // per_block if per_block > 0 else cap
        cap = max(self.pipe.prefetch, min(cap, int(fit)))
        if (self.cache is not None and self._act_keys is not None
                and self.cache.planned_fraction(self._act_keys,
                                                self._act_sizes) > 0.5):
            # The epochs this tune governs are majority cache-hit: most
            # blocks never cross the bus, so a deeper H2D queue buys nothing
            # and only holds extra HBM — keep the depth where it is.
            cap = self.pipe.prefetch
        put = self.stats.put_seconds - self._put_mark
        drain = self.stats.drain_seconds - self._drain_mark
        self.pipe.prefetch = tune_prefetch(put, drain, self.pipe.prefetch,
                                           cap)

    # ---------------------------------------------------------- shared passes
    def begin_pass(self, kind: str) -> None:
        """``kind``: "init" (warm-start w accumulation), "full" (violation-
        collecting epoch), "cheap" (uncompacted non-full epoch), or "compact"
        (engine-local compacted epoch).  Pending ladder tasks ride any
        FULL-COVERAGE pass (init/full/cheap — never compact) as pure
        `_accum_w` windows and join the sweep from the next epoch."""
        self._kind = kind
        self._init_live = list(self.pending_init) if kind != "compact" else []
        if kind == "init":
            self._live = []
        else:
            self._live = [t for t in range(self.T)
                          if self.active[t] and not self.done[t]]
        self._viol = {t: [] for t in self._live}
        self._put_mark = self.stats.put_seconds
        self._drain_mark = self.stats.drain_seconds

    def _h2d(self, a):
        """The engine's H2D put with the transient-retry policy: under
        `fail_fast` (default) this is exactly `_put` plus the fault-injection
        probe; with retries enabled, transient failures back off
        exponentially and re-issue the put — `_put` never partially applies
        (`jax.device_put` either returns an array or raises), so a retry is
        bit-identical to a first-try success."""
        attempt = 0
        while True:
            try:
                _fault_check("h2d", device=self.name, epoch=self._epoch)
                out = _put(a, self.device)
            except Exception as exc:
                if (attempt >= self._retries
                        or classify_error(exc) != "transient"):
                    raise
                self.trace.instant("fault", "h2d_retry", device=self.name,
                                   attempt=attempt,
                                   error=type(exc).__name__)
                delay = self._backoff * (2.0 ** attempt)
                if delay > 0:
                    time.sleep(delay)
                attempt += 1
                continue
            if attempt:
                self.trace.instant("recovery", "h2d_retry_ok",
                                   device=self.name, attempts=attempt)
            return out

    def _put_block(self, gb_send, cache_key: Optional[bytes] = None):
        t0 = self.trace.begin()
        if isinstance(gb_send, QuantBlock):
            # int8 wire: ship values + compact scale table, dequantise fused
            # on device — a quarter of the f32 bytes crossed the bus.
            vals = self._h2d(gb_send.values)
            scales = self._h2d(gb_send.scales)
            self.stats.put_seconds += self.trace.end(
                "h2d", "put_block", t0, bytes=int(gb_send.nbytes))
            self.stats.bytes_put += gb_send.nbytes
            if cache_key is not None:
                # Pin the WIRE arrays (int8 codes + scale table, a quarter
                # of the f32 residency); dequant stays fused per use.
                self._cache_store(cache_key, (vals, scales, gb_send.group),
                                  gb_send.nbytes)
            return dequant_rows(vals, scales, gb_send.group)
        gb = self._h2d(gb_send)
        self.stats.put_seconds += self.trace.end(
            "h2d", "put_block", t0, bytes=int(gb_send.nbytes))
        self.stats.bytes_put += gb_send.nbytes
        if cache_key is not None:
            # Pin the device array exactly as put (bf16 stays bf16 — the
            # upcast is re-run per use, same as the streamed path), so a
            # cached block decodes bit-identically to a shipped one.
            self._cache_store(cache_key, gb, gb_send.nbytes)
        return _upcast32(gb) if self._bf16 else gb

    def _cache_store(self, key: bytes, payload, nbytes: int) -> None:
        if self.cache is not None and self.cache.put(key, payload, nbytes):
            self.stats.cache_resident_bytes = self.cache.peak_resident_bytes

    def _decode_cached(self, payload):
        """Re-run the per-use decode step on a pinned payload — the SAME ops
        the miss path applies after its H2D put, so hit and miss blocks are
        bit-identical inputs to the epoch kernel."""
        if isinstance(payload, tuple):
            vals, scales, group = payload
            return dequant_rows(vals, scales, group)
        return _upcast32(payload) if self._bf16 else payload

    def _put_vec(self, vec, fill, dtype, length):
        t0 = self.trace.begin()
        b = self._h2d(_padded(np.asarray(vec), fill, dtype, length))
        self.stats.put_seconds += self.trace.end(
            "h2d", "put_vec", t0, bytes=int(b.nbytes))
        self.stats.bytes_h2d += b.nbytes
        self.stats.bytes_put += b.nbytes
        return b

    def feed_block(self, sel, cnt, gb_send) -> None:
        """Process one shared-pass block handed over by the driver's reader.
        The G bytes were staged (and accounted) once by the reader; only this
        engine's task-vector traffic is counted here."""
        gb = self._put_block(gb_send)
        b = sel.start // self.tile
        if self._init_live:
            # Pending ladder tasks: accumulate w0 from the task's window of
            # this block — w0 += (alpha * y) @ G[window] — while live tasks
            # sweep the same staged bytes below.
            for t in self._init_live:
                lo, hi = int(self.bounds[t][b]), int(self.bounds[t][b + 1])
                if lo == hi:
                    continue
                m = hi - lo
                wl = _win_pad(m)
                rl = (self.ids[t][lo:hi] - sel.start).astype(np.int32)
                rlb = self._put_vec(rl, 0, np.int32, wl)
                ab = self._put_vec(self.a_r[t][lo:hi], 0.0, np.float32, wl)
                yb = self._put_vec(self.y_r[t][lo:hi], 1.0, np.float32, wl)
                self.w[t] = _accum_w(self.w[t], _gather_rows(gb, rlb), ab, yb)
                self.stats.kernel_calls += 1
        if self._kind == "init" or not self._live:
            return
        qb = _row_sq(gb)
        base = sel.start
        items = []
        for t in self._live:
            lo, hi = int(self.bounds[t][b]), int(self.bounds[t][b + 1])
            if lo == hi:
                continue
            rl = (self.ids[t][lo:hi] - base).astype(np.int32)
            items.append(self._sweep_window(gb, qb, t, slice(lo, hi), rl,
                                            full=(self._kind == "full")))
        self.pipe.push(items)

    def _sweep_window(self, gb, qb, t, take, rl, *, full: bool):
        """Run the epoch kernel over ONE task's window of a staged block:
        gather the task's rows (and their q) on device, sweep only them.
        ``take`` addresses the window in the task-LOCAL arrays (a contiguous
        slice on full passes, an active-position gather on compacted
        epochs); ``rl`` holds the block-local row ids.  Windows are padded
        to a pow2 bucket (`_win_pad`) with inert c = 0 rows so kernels
        compile per bucket, not per ragged size."""
        m = len(rl)
        wl = _win_pad(m)
        rlb = self._put_vec(rl, 0, np.int32, wl)
        gw, qw = _window(gb, qb, rlb)
        ab = self._put_vec(self.a_r[t][take], 0.0, np.float32, wl)
        yb = self._put_vec(self.y_r[t][take], 1.0, np.float32, wl)
        cb = self._put_vec(self.c_r[t][take], 0.0, np.float32, wl)
        ub = self._put_vec(self.u_r[t][take], 0, np.int32, wl)
        t0 = self.trace.begin()
        a2, u2, w2, viol = self.epoch_fn(
            gw, yb, cb, qw, ab, ub, self.w[t],
            full_pass=full, shrink_k=self.shrink_k)
        self.w[t] = w2
        self.trace.end("kernel", "sweep_window", t0, rows=m, task=t)
        self.stats.kernel_calls += 1
        self.stats.coord_visits += m
        if full:
            self._viol[t].append(viol)
        return (t, take, m, a2, u2)

    def end_pass(self) -> None:
        self.pipe.flush()
        newly = self._init_live
        self._init_live = []
        if self._kind == "full":
            self.stats.full_passes += 1
            for t in self._live:
                # Empty generators (a task with no real rows, or none inside
                # this shard's blocks) converge trivially — exactly what the
                # old inert-padded sweep reported for them.
                v = max((float(np.asarray(r)) for r in self._viol[t]),
                        default=0.0)
                self.violation[t] = v
                if v < self.config.tol:
                    self.done[t] = True
                    self.epochs_used[t] = (self._epoch + 1
                                           - self.first_sweep[t])
                    s = int(self.chain_next[t])
                    if (s >= 0 and not self.active[s] and not self.done[s]
                            and s not in self.pending_init):
                        # Seed the ladder successor: the converged cell's
                        # alphas clipped into the next C box — the same
                        # warm chain serial `grid_search` builds, but the
                        # retired cell's farm slot frees immediately.
                        self.a_r[s][:] = np.clip(self.a_r[t], 0.0,
                                                 self.c_r[s])
                        self.u_r[s][:] = 0
                        if self.a_r[s].size and self.a_r[s].any():
                            self.pending_init.append(s)
                        else:
                            self.active[s] = True
                            self.first_sweep[s] = self._epoch + 1
        # Promote tasks whose w0 finished accumulating THIS pass: they sweep
        # from the next epoch and their local epoch count starts there.
        for t in newly:
            self.pending_init.remove(t)
            self.active[t] = True
            self.first_sweep[t] = self._epoch + 1
        if self._kind != "full":
            return
        self._recompact()

    def _recompact(self, record: bool = True) -> None:
        """Rebuild the compacted cheap-epoch state from the current
        unchanged-counters — a pure function of post-full-pass solver state,
        which is why checkpoints snapshot only that state and re-run this at
        restore (``record=False``: skip the stats/history appends the
        boundary's carry already contains).

        Cheap epochs then stream only rows active for at least one
        unconverged task — shrinking cuts H2D bytes, not just FLOPs."""
        t0 = self.trace.begin()
        self.act, self.act_G, self.act_q = None, None, None
        self._cw = {}
        self._act_keys = self._act_sizes = None
        live2 = [t for t in range(self.T)
                 if self.active[t] and not self.done[t]]
        if self.config.shrink and live2:
            act_take = {t: np.where(self.u_r[t] < self.shrink_k)[0]
                        for t in live2}
            union = np.unique(np.concatenate(
                [self.ids[t][act_take[t]] for t in live2]))
            if record:
                self.stats.active_history.append(int(len(union)))
            if len(union) < self.n:
                self.act = union
                # Gather (and, for bf16/int8 wire blocks, re-encode) ONCE
                # per compaction — the cheap epochs between full passes then
                # slice pass-through views (bf16/f32) or reuse the per-block
                # quantised shadow (int8) instead of re-encoding per epoch.
                # G itself stays f32: a persistent reduced-precision shadow
                # of the whole factor would cost +25-50% of the dominant
                # host allocation.
                act_G = self.G[union]
                if self._wire == "int8":
                    self.act_q = self._encode_compacted(union, act_G)
                else:
                    self.act_G = (act_G.astype(BLOCK_DTYPES["bf16"])
                                  if self._bf16 else act_G)
                n_blocks = math.ceil(max(len(union), 1) / self.tile)
                tile = self.tile
                # Per-task compacted windows: each live task's ACTIVE rows
                # mapped to their sorted union positions, with a
                # searchsorted boundary table over those positions —
                # restricting a task to its compaction-time active rows is
                # trajectory-identical to sweeping them as kernel no-ops
                # (an inactive row cannot reactivate between full passes).
                for t in live2:
                    ap = act_take[t]
                    pos = np.searchsorted(union, self.ids[t][ap])
                    self._cw[t] = (ap, pos,
                                   block_windows(pos, tile, n_blocks))
                if self.cache is not None:
                    # Re-plan the HBM pin set for the new union: keys are
                    # content-addressed by global row ids, so blocks whose
                    # row set survived the re-compaction keep their pinned
                    # device arrays (immediate hits); the rest are evicted
                    # here and re-pinned lazily by the first cheap epoch's
                    # misses.  Ranking is violation recency — hottest
                    # (most recently violating) blocks pin first when the
                    # union exceeds the cache budget.
                    self._act_keys = [
                        block_key(union[b * tile:(b + 1) * tile], self._wire)
                        for b in range(n_blocks)]
                    if self.act_q is not None:
                        self._act_sizes = [q.nbytes for q in self.act_q]
                    else:
                        blk_nb = (tile * self.rank
                                  * self._stage.buf.dtype.itemsize)
                        self._act_sizes = [blk_nb] * n_blocks
                    self.cache.plan(
                        self._act_keys, self._act_sizes,
                        violation_recency_scores_tasks(
                            union, tile,
                            [self.u_r[t][act_take[t]] for t in live2],
                            [self.ids[t][act_take[t]] for t in live2]))
                    self.stats.cache_evictions = self.cache.evictions
                    if record:
                        self.trace.instant(
                            "cache", "plan", blocks=n_blocks,
                            evictions=self.cache.evictions,
                            resident_bytes=self.cache.resident_bytes)
        if self.cache is not None and self._act_keys is None:
            # No compaction to serve (union == n, all tasks converged, or
            # shrinking off): nothing the cache could hit — drop the pins.
            self.cache.invalidate()
            self.stats.cache_evictions = self.cache.evictions
            if record:
                self.trace.instant("cache", "invalidate",
                                   evictions=self.cache.evictions)
        self.trace.end(
            "compact", "recompact", t0,
            union=int(len(self.act)) if self.act is not None else self.n,
            tasks=len(live2))

    # ----------------------------------------------------- compacted epochs
    def _encode_compacted(self, union: np.ndarray,
                          act_G: np.ndarray) -> List[QuantBlock]:
        """Quantised shadow of the compacted active rows, encoded ONCE per
        compaction and reused by every cheap epoch until the next.

        Each row keeps the (scale, zero) of its GLOBAL row group — the
        same entry its shared-pass block used (`wire_group` aligns group and
        block boundaries) — so the decoded value of a row is identical (to
        FMA rounding) between full passes and compacted cheap epochs.  The
        solver then
        optimises ONE consistent perturbed problem; re-grouping the gathered
        rows instead would re-quantise them against different stats and the
        full-pass KKT check could stall above tolerance forever.  The wire
        pays per-ROW scale entries (group=1) only on these gathered blocks.
        """
        gscales = self._scale_cache.get("gscales")
        if gscales is None:
            # A shard-backed G computes the table shard-by-shard on disk
            # (same values: shard boundaries are group-aligned); a host
            # ndarray takes the direct reduction.
            gs_fn = getattr(self.G, "group_scales", None)
            gscales = (gs_fn(self._group) if callable(gs_fn)
                       else group_scales(self.G, self._group))
            self._scale_cache["gscales"] = gscales
        srow = gscales[union // self._group]              # (n_act, 2)
        vals = encode_rows(act_G, srow)
        tile = self.tile
        out = []
        for b in range(math.ceil(max(len(union), 1) / tile)):
            s, e = b * tile, min((b + 1) * tile, len(union))
            qb = QuantBlock(values=vals[s:e], scales=srow[s:e], group=1)
            out.append(qb if e - s == tile else pad_quant_block(qb, tile))
        return out

    def run_cheap_epoch(self) -> None:
        """One engine-local non-full epoch over the shard's own compacted
        active-row union (the driver only calls this when `act` is set; an
        empty union makes the epoch a no-op)."""
        rows = self.act
        if rows is None or len(rows) == 0:
            return
        self.begin_pass("compact")
        tile = self.tile
        for b in range(math.ceil(len(rows) / tile)):
            s, e = b * tile, min((b + 1) * tile, len(rows))
            key = self._act_keys[b] if self._act_keys is not None else None
            ent = self.cache.lookup(key) if key is not None else None
            if ent is not None:
                # Cache hit: the block's wire arrays are already pinned in
                # HBM — decode per use, ZERO G bytes cross the bus (the
                # transfer-guard test in tests/test_block_cache.py pins
                # this down).
                self.stats.bytes_hit += ent.nbytes
                self.stats.cache_hits += 1
                self.trace.instant("cache", "hit", bytes=int(ent.nbytes),
                                   block=b)
                gb = self._decode_cached(ent.payload)
            else:
                gb_send = (self.act_q[b] if self.act_q is not None
                           else prep_block(self.act_G[s:e], tile,
                                           self.cfg.block_dtype, self._group,
                                           self._stage))
                self.stats.bytes_h2d += gb_send.nbytes
                self.stats.bytes_g += gb_send.nbytes
                self.stats.bytes_miss += gb_send.nbytes
                if isinstance(gb_send, QuantBlock):
                    self.stats.bytes_scales += gb_send.scale_bytes
                self.stats.blocks_streamed += 1
                self.stats.rows_streamed += e - s
                if self.cache is not None:
                    self.stats.cache_misses += 1
                    self.trace.instant("cache", "miss",
                                       bytes=int(gb_send.nbytes), block=b)
                gb = self._put_block(gb_send, cache_key=key)
            qb = _row_sq(gb)
            items = []
            for t in self._live:
                cw = self._cw.get(t)
                if cw is None:
                    continue
                ap, pos, bnd = cw
                lo, hi = int(bnd[b]), int(bnd[b + 1])
                if lo == hi:
                    continue
                # ``take`` gathers the task-local arrays at the window's
                # active positions; ``rl`` maps them to union-block rows.
                take = ap[lo:hi]
                rl = (pos[lo:hi] - s).astype(np.int32)
                items.append(self._sweep_window(gb, qb, t, take, rl,
                                                full=False))
            self.pipe.push(items)
        self.pipe.flush()

    # -------------------------------------------------------------- results
    def result(self):
        """Assemble this shard's `SolveResult` (host numpy, same layout as
        `solve_batch`) and its per-device stats record."""
        t0 = self.trace.begin()
        W = (np.stack([np.asarray(wt) for wt in self.w]) if self.T
             else np.zeros((0, self.rank), np.float32))
        self.stats.bytes_d2h += W.nbytes
        alpha = np.zeros_like(self.a0_loc)
        for t in range(self.T):
            alpha[t][self.scat[t]] = self.a_r[t]
        self.trace.end("scatter", "result", t0,
                       bytes=int(W.nbytes + alpha.nbytes), tasks=self.T)
        asum = (np.array([self.a_r[t].sum() for t in range(self.T)],
                         np.float32) if self.T
                else np.zeros((0,), np.float32))
        dual = asum - 0.5 * (W * W).sum(axis=1)
        n_sv = (alpha > 0.0).sum(axis=1).astype(np.int32)
        self.stats.epochs = self.epochs_run
        self.stats.prefetch_final = self.pipe.prefetch
        res = SolveResult(alpha=alpha, w=W.astype(np.float32),
                          epochs=self.epochs_used, violation=self.violation,
                          dual_obj=dual.astype(np.float32), n_sv=n_sv)
        return res, self.stats


class _InlineFanout:
    """Single-engine degenerate of the per-device worker fan-out: feed blocks
    on the calling thread (zero overhead at one device)."""

    def submit(self, engine, fn):
        fn()

    def barrier(self):
        pass

    def close(self, suppress: bool = False):
        pass


def drive_streamed_engines(engines: Sequence[_Stage2Engine], G, config:
                           SolverConfig, cfg: StreamConfig, *, tile: int,
                           fanout=None, guard=None) -> Stage2StreamStats:
    """Lockstep epoch driver over one or more engines.

    Reads each (tile, B) block of G ONCE per shared pass (warm-start init,
    full epochs, and uncompacted cheap epochs) and fans it out to every live
    engine via ``fanout`` (inline for one engine, per-device host workers for
    the overlapped farm), so per-pass G traffic is independent of device
    count.  Compacted cheap epochs run engine-locally and concurrently.
    Returns the shared-reader stats record (G-block traffic + epoch/pass
    counters); per-engine records accumulate task-vector traffic.

    ``guard`` (a `resilience.StreamGuard`) adds fault tolerance: epoch-
    boundary snapshots every `checkpoint_every` full passes, an in-memory
    degradation snapshot, and resume — the loop starts at the guard's
    ``start_epoch`` and the init pass is skipped when a restored snapshot
    already accumulated w0 (resumed ladder successors in ``pending_init``
    instead ride the next promoted full pass, exactly as the uninterrupted
    run would).
    """
    fan = fanout or _InlineFanout()
    tr = resolve_tracer(cfg.trace)
    reader = Stage2StreamStats(tile_rows=tile, block_dtype=cfg.block_dtype)
    # One reusable pad buffer for every shared pass of this solve: the
    # barrier below guarantees the previous pass's tail has been consumed.
    stage = _PadStage(tile, G.shape[1], cfg.block_dtype)

    def shared_pass(group, kind):
        g0 = reader.bytes_h2d
        for e in group:
            e.begin_pass(kind)
        for sel, cnt, gb in iter_shared_blocks(G, tile, cfg.block_dtype,
                                               wire_group(tile, cfg), stage,
                                               trace=tr):
            reader.bytes_h2d += gb.nbytes
            reader.bytes_g += gb.nbytes
            if isinstance(gb, QuantBlock):
                reader.bytes_scales += gb.scale_bytes
            reader.blocks_streamed += 1
            reader.rows_streamed += cnt
            for e in group:
                fan.submit(e, partial(e.feed_block, sel, cnt, gb))
        for e in group:
            fan.submit(e, e.end_pass)
        fan.barrier()
        return reader.bytes_h2d - g0

    ok = False
    try:
        if guard is not None:
            guard.on_start(engines, reader)
        init = [e for e in engines if e.needs_init]
        if init and (guard is None or not guard.init_done):
            # Resume skips this: a restored snapshot already holds the
            # accumulated w0 (restored `pending_init` tasks are ladder
            # successors seeded at the boundary — their w0 rides the next
            # promoted FULL pass, never a fresh init pass, so their
            # `first_sweep` anchors match the uninterrupted run).
            shared_pass(init, "init")   # init traffic counts, but no epoch
        if guard is not None and not guard.init_done:
            guard.mark_init(engines, reader)

        period = config.full_pass_period if config.shrink else 1
        tuned = not cfg.autotune_prefetch
        start = guard.start_epoch if guard is not None else 0
        for epoch in range(start, config.max_epochs):
            live = [e for e in engines if not e.all_done]
            if not live:
                break
            for e in live:
                e.start_epoch(epoch)
            if tr.enabled:
                te0 = tr.begin()
                cv0 = sum(e.stats.coord_visits for e in live)
            full = ((epoch % period == 0) or not config.shrink
                    or any(e.wants_full for e in live))
            # ^ freshly seeded C-ladder successors need a full-coverage pass
            #   for their w0 accumulation — promote rather than let them
            #   idle until the next scheduled full pass
            if full:
                reader.epoch_bytes.append(shared_pass(live, "full"))
                reader.full_passes += 1
                if not tuned:
                    tuned = True
                    for e in live:
                        e.autotune(cfg.prefetch_cap)
            else:
                # Engines WITH a compacted union stream their own gathered
                # rows; the rest (nothing shrunk yet) share one G read.
                own = [e for e in live if e.act is not None]
                shared = [e for e in live if e.act is None]
                for e in own:
                    fan.submit(e, e.run_cheap_epoch)
                if shared:
                    reader.epoch_bytes.append(shared_pass(shared, "cheap"))
                else:
                    fan.barrier()
                    reader.epoch_bytes.append(0)
            for e in live:
                e.finish_epoch(epoch)
            if guard is not None and full:
                # Snapshot AFTER finish_epoch (and after end_pass's ladder
                # seeding + re-compaction) — the boundary state restore
                # replays from; the kill probe sits after the save so a
                # killed run always has this boundary on disk.
                guard.on_boundary(engines, reader, epoch, trace=tr)
            _fault_check("epoch_boundary", epoch=epoch)
            if tr.enabled:
                _trace_epoch(tr, te0, epoch, "full" if full else "cheap",
                             live, reader, cv0)
        ok = True
    finally:
        # On the failure path close() must not raise over the propagating
        # exception — stuck workers are reported as a trace instant/warning
        # instead (see _DeviceWorkers.close).
        fan.close(suppress=not ok)
    return reader


def _trace_epoch(tr, t0: float, epoch: int, kind: str,
                 live: Sequence[_Stage2Engine], reader: Stage2StreamStats,
                 cv0: int) -> None:
    """Close the driver's per-epoch span: attrs aggregate the epoch's
    traffic/convergence counters across live engines — the `--verbose`
    progress listener and the trace-file epoch row both read from it."""
    eb = reader.epoch_bytes[-1] if reader.epoch_bytes else 0
    hit = miss = 0
    for e in live:
        eb += e.stats.epoch_bytes[-1] if e.stats.epoch_bytes else 0
        hit += e.stats.epoch_hit_bytes[-1] if e.stats.epoch_hit_bytes else 0
        miss += (e.stats.epoch_miss_bytes[-1]
                 if e.stats.epoch_miss_bytes else 0)
    rows = sum(e.stats.coord_visits for e in live) - cv0
    act = sum((len(e.act) if e.act is not None else e.n) for e in live)
    viols = np.concatenate([e.violation for e in live])
    viols = viols[np.isfinite(viols)]
    attrs = dict(epoch=epoch, kind=kind, bytes=int(eb), hit_bytes=int(hit),
                 miss_bytes=int(miss), rows=int(rows), active=int(act),
                 devices=len(live))
    if viols.size:
        attrs["viol"] = float(viols.max())
    tr.end("epoch", f"epoch_{epoch}", t0, **attrs)
    tr.counter("stage2/epoch_bytes", eb)
    tr.counter("stage2/active_rows", act)
    tr.counter("stage2/row_visits", rows)


def _elementwise_sum(lists: Sequence[Sequence[int]]) -> List[int]:
    out: List[int] = []
    for li in lists:
        for i, v in enumerate(li):
            if i < len(out):
                out[i] += v
            else:
                out.append(v)
    return out


def merge_stream_stats(reader: Stage2StreamStats,
                       per_dev: Sequence[Stage2StreamStats], *,
                       seconds: float, n_devices: int,
                       carry=None) -> Stage2StreamStats:
    """Aggregate the shared-reader record and the per-device engine records
    into the mesh-level `Stage2StreamStats`.  G blocks staged by the shared
    reader are counted ONCE in `bytes_h2d` (that is the point: per-pass
    unique G traffic does not scale with device count); task-vector traffic
    and compacted-epoch gathers sum over devices because they are
    partitioned, not replicated; `bytes_put` sums every device's physical
    DMA copies (== `bytes_h2d` at one device, G component ~D x beyond).

    ``carry`` is a `resilience` stats-carry tree of the segments BEFORE a
    resume (or device-quarantine restart): counters sum, per-epoch lists are
    prepended, so the merged record reads like one uninterrupted run.  Stats
    of a failed partial pass are rolled back to the last epoch boundary with
    the solver state — each `epoch_bytes` entry remains a COMPLETED pass's
    figure, which is what the device-count-invariance claim is asserted on."""
    out = Stage2StreamStats(tile_rows=reader.tile_rows,
                            block_dtype=reader.block_dtype,
                            n_devices=n_devices)
    out.bytes_h2d = reader.bytes_h2d
    out.bytes_g = reader.bytes_g
    out.bytes_scales = reader.bytes_scales
    out.blocks_streamed = reader.blocks_streamed
    out.rows_streamed = reader.rows_streamed
    for s in per_dev:
        out.bytes_h2d += s.bytes_h2d
        out.bytes_g += s.bytes_g
        out.bytes_scales += s.bytes_scales
        out.bytes_put += s.bytes_put
        out.bytes_d2h += s.bytes_d2h
        out.blocks_streamed += s.blocks_streamed
        out.rows_streamed += s.rows_streamed
        out.kernel_calls += s.kernel_calls
        out.coord_visits += s.coord_visits
        out.put_seconds += s.put_seconds
        out.drain_seconds += s.drain_seconds
        # Cache traffic is engine-local (compacted unions are partitioned
        # per shard), so it sums like the other partitioned traffic.
        out.bytes_hit += s.bytes_hit
        out.bytes_miss += s.bytes_miss
        out.cache_hits += s.cache_hits
        out.cache_misses += s.cache_misses
        out.cache_evictions += s.cache_evictions
        out.cache_resident_bytes += s.cache_resident_bytes
    out.epochs = max((s.epochs for s in per_dev), default=0)
    out.full_passes = max((s.full_passes for s in per_dev),
                          default=reader.full_passes)
    out.epoch_bytes = _elementwise_sum([reader.epoch_bytes]
                                       + [s.epoch_bytes for s in per_dev])
    out.epoch_hit_bytes = _elementwise_sum([s.epoch_hit_bytes
                                            for s in per_dev])
    out.epoch_miss_bytes = _elementwise_sum([s.epoch_miss_bytes
                                             for s in per_dev])
    # Shard unions can OVERLAP in rows (one class's rows are active in every
    # pair that references it, across shards), so this sum is the total rows
    # each cheap epoch streams farm-wide — an upper bound on the true union
    # that may exceed n; per-shard unions live in `per_device`.
    out.active_history = _elementwise_sum([s.active_history for s in per_dev])
    out.prefetch_final = max((s.prefetch_final for s in per_dev), default=0)
    out.seconds = seconds
    out.per_device = list(per_dev) if n_devices > 1 else None
    if carry is not None:
        from repro.core.resilience import apply_carry
        apply_carry(out, carry)
    return out


def solve_batch_streamed(
    G,
    tasks: TaskBatch,
    config: SolverConfig = SolverConfig(),
    *,
    stream_config: Optional[StreamConfig] = None,
    epoch_fn: Optional[Callable] = None,
    device=None,
    chain_next=None,
    return_stats: bool = False,
):
    """Drop-in `solve_batch` over a host-resident G (numpy buffer).

    G row-blocks of `tile` rows stream through `epoch_fn` (the SMO epoch
    kernel contract) with per-task w chained on device; alpha/unchanged live
    on host and are scattered back per block.  ``chain_next`` optionally
    declares C-ladder warm-start chains over the task axis (see the module
    docstring).  Returns a `SolveResult` whose fields are host numpy arrays
    (same shapes/layout as `solve_batch`), plus a `Stage2StreamStats` when
    ``return_stats=True``.  One-engine instantiation of the shared
    engine/driver; the overlapped multi-device farm lives in
    `core/distributed.py::solve_tasks_streamed`.
    """
    t_start = time.perf_counter()
    cfg = stream_config or StreamConfig()
    if epoch_fn is None:
        epoch_fn = default_epoch_fn()
    if not getattr(G, "is_shard_view", False):
        # A shards.GShardView stays on disk: asarray would materialise the
        # full (n, rank) factor and defeat the spill.  Its slice/gather
        # surface feeds the reader below directly.
        G = np.asarray(G, np.float32)
    n, rank = G.shape
    tile = auto_tile_rows(n, rank, tasks.n_tasks, cfg)
    eng = _Stage2Engine(G, tasks, config, cfg, epoch_fn=epoch_fn,
                        device=device, tile=tile, chain_next=chain_next)
    guard = None
    if cfg.checkpoint_dir:
        from repro.core.resilience import (StreamGuard, g_fingerprint,
                                           restore_engines)
        sizes = np.array([len(eng.ids[t]) for t in range(eng.T)], np.int64)
        guard = StreamGuard(cfg, n=n, rank=rank, sizes=sizes,
                            g_fp=g_fingerprint(G))
        if cfg.resume:
            snap = guard.try_resume()
            if snap is not None:
                guard.adopt(snap)
                restore_engines([eng], snap)
    reader = drive_streamed_engines([eng], G, config, cfg, tile=tile,
                                    guard=guard)
    res, est = eng.result()
    if not return_stats:
        return res
    stats = merge_stream_stats(reader, [est],
                               seconds=time.perf_counter() - t_start,
                               n_devices=1,
                               carry=guard.carry if guard else None)
    return res, stats


def solve_streamed_auto(
    G,
    tasks: TaskBatch,
    config: SolverConfig = SolverConfig(),
    *,
    stream_config: Optional[StreamConfig] = None,
    chain_next=None,
    return_stats: bool = False,
    resume: Optional[bool] = None,
):
    """The streamed stage-2 entry point every routed caller (`LPDSVM.fit`,
    `core/cv.py`, `solve_polished`'s final level, the CLI) goes through: with
    more than one local device the multi-device task farm — overlapped
    behind the shared block reader by default, or serial per-device streams
    when `StreamConfig.overlap_devices` is off — otherwise the single-device
    block stream.  ``resume`` overrides `StreamConfig.resume`: continue from
    the latest epoch-boundary snapshot in `StreamConfig.checkpoint_dir`."""
    cfg = stream_config or StreamConfig()
    if resume is not None and resume != cfg.resume:
        cfg = dataclasses.replace(cfg, resume=bool(resume))
    devices = jax.local_devices()
    if len(devices) > 1 and tasks.n_tasks > 1:
        from repro.core.distributed import solve_tasks_streamed
        return solve_tasks_streamed(G, tasks, config, devices=devices,
                                    stream_config=cfg,
                                    overlap=cfg.overlap_devices,
                                    chain_next=chain_next,
                                    return_stats=return_stats)
    return solve_batch_streamed(G, tasks, config, stream_config=cfg,
                                chain_next=chain_next,
                                return_stats=return_stats)
