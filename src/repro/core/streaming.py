"""Out-of-core stage 1: stream the Nyström factor G in row chunks.

The paper's "more RAM" ingredient: the dataset and the (n, B') factor G live
in *host* memory (512 GB class), while the accelerator only ever holds one
row chunk's working set — the landmark block, the projector, and a few chunks
in flight.  That decouples the trainable n from device memory:

    host RAM                          device HBM
    ────────────────────────────      ─────────────────────────────
    x        (n, p)   read-only       landmarks  (B, p)    resident
    G        (n, B')  preallocated    projector  (B, B')   resident
                                      per chunk: x[s:e], K_chunk, G_chunk

The streaming loop exploits jax's async dispatch as the double buffer:
``jax.device_put`` of chunk k+1 and the Pallas ``gram`` launch for it are
enqueued while chunk k's result is still being fetched to host — the host
only blocks on the *oldest* in-flight chunk (``prefetch`` controls the queue
depth).  On TPU/GPU that overlaps H2D copy, MXU compute, and D2H copy; on the
CPU container it degrades gracefully to sequential execution with identical
numerics, which is what the tests pin down.

Passing ``devices`` round-robins disjoint chunk streams over several devices
(each with its own resident landmark/projector replica) —
`core/distributed.py` wraps that for a mesh.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from functools import partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.faults import check as _fault_check
from repro.core.kernel_fn import HIGHEST, KernelParams, gram
from repro.core.quant import (GROUP_ROWS, QuantBlock, dequantize_rows,
                              quantize_rows)
from repro.core.trace import resolve as resolve_tracer

BYTES_F32 = 4

WIRE_DTYPES = ("f32", "bf16", "int8")


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Knobs of the chunked stage-1 pipeline (all sizes in rows / bytes).

    ``device_budget_bytes`` is the stage-1 *working set* allowance on one
    device, not the physical HBM size — leave headroom for the stage-2 solver
    (G rows get re-materialised there) and the runtime itself.
    """

    device_budget_bytes: int = 2 << 30   # 2 GiB default working-set allowance
    chunk_rows: Optional[int] = None     # None -> derived from the budget
    prefetch: int = 2                    # chunks in flight (double buffering)
    min_chunk_rows: int = 256
    tile_rows: Optional[int] = None      # stage-2 G block rows (None -> derived)
    block_dtype: str = "f32"             # wire dtype of streamed stage-2 G
                                         # blocks: "f32", "bf16" (half H2D,
                                         # upcast on device) or "int8"
                                         # (quarter H2D, per-row-group
                                         # scale/zero codec, device dequant)
    stage1_dtype: str = "f32"            # wire dtype of streamed stage-1 x
                                         # chunks: "f32" or "int8" (symmetric
                                         # codec; dequant fused into the gram
                                         # kernel)
    quant_group_rows: int = GROUP_ROWS   # rows per int8 scale group (both
                                         # stages; 8 scale bytes per group)
    overlap_devices: bool = True         # >1 local device: overlapped task
                                         # farm behind one shared block reader
    autotune_prefetch: bool = True       # deepen the in-flight queue when the
                                         # first full pass is transfer-bound
    prefetch_cap: int = 8                # autotune ceiling on queue depth
    cache_blocks: bool = True            # pin the shrinking-compacted active
                                         # row union device-side (HBM block
                                         # cache); safe default — cached
                                         # blocks decode bit-identically to
                                         # streamed ones
    cache_budget_bytes: Optional[int] = None  # HBM cache allowance per
                                         # engine; None -> the unused
                                         # remainder of device_budget_bytes
    trace: Optional[object] = None       # core.trace.Tracer recording the
                                         # pipeline timeline; None -> the
                                         # process-wide tracer if installed,
                                         # else the no-op fast path
    # -- fault tolerance (core/resilience.py) --------------------------------
    checkpoint_dir: Optional[str] = None  # where stage-2 epoch snapshots and
                                         # the resumable stage-1 memmap live;
                                         # None -> checkpointing off
    checkpoint_every: int = 0            # full passes between stage-2 disk
                                         # snapshots (0 = never snapshot)
    resume: bool = False                 # continue from the latest snapshot /
                                         # completed stage-1 chunk ranges in
                                         # checkpoint_dir
    fail_fast: bool = True               # True (default): any worker error
                                         # kills the solve (pre-PR semantics).
                                         # False: transient H2D errors retry
                                         # with backoff, lost devices are
                                         # quarantined and their task shard
                                         # re-split onto survivors from the
                                         # last epoch-boundary snapshot
    max_retries: int = 3                 # bounded transient-H2D retries per
                                         # put (only when fail_fast=False)
    retry_backoff: float = 0.05          # base seconds of the exponential
                                         # retry backoff (doubles per attempt)
    watchdog_seconds: float = 0.0        # farm-barrier starvation watchdog:
                                         # raise a queue/thread diagnostic
                                         # instead of hanging (0 = off)
    checkpoint_keep: int = 3             # stage-2 snapshots retained on disk
                                         # (keep-last-k, delete-after-write;
                                         # 0 = keep every step_*.msgpack)
    # -- disk tier (core/shards.py) ------------------------------------------
    shard_dir: Optional[str] = None      # root of the checksummed shard
                                         # store(s); None -> disk tier off
    shard_rows: int = 4096               # rows per shard file (multiple of
                                         # quant.GROUP_ROWS so int8 scale
                                         # groups stay global-row-aligned)
    spill_g: bool = False                # stream stage-1 G into f32 shards
                                         # under shard_dir and read it back
                                         # in stage 2 (host G never built)
    verify_shards: bool = True           # recompute each shard's checksum on
                                         # every disk read (False = trust
                                         # the bytes; bench the difference)

    def __post_init__(self):
        if self.prefetch < 1:
            raise ValueError("prefetch must be >= 1")
        if self.chunk_rows is not None and self.chunk_rows < 1:
            raise ValueError("chunk_rows must be positive")
        if self.tile_rows is not None and self.tile_rows < 1:
            raise ValueError("tile_rows must be positive")
        if self.block_dtype not in WIRE_DTYPES:
            raise ValueError(f"block_dtype must be one of {WIRE_DTYPES}, "
                             f"got {self.block_dtype!r}")
        if self.stage1_dtype not in ("f32", "int8"):
            raise ValueError(f"stage1_dtype must be 'f32' or 'int8', "
                             f"got {self.stage1_dtype!r}")
        if self.quant_group_rows < 1:
            raise ValueError("quant_group_rows must be >= 1")
        if self.prefetch_cap < 1:
            raise ValueError("prefetch_cap must be >= 1")
        if self.cache_budget_bytes is not None and self.cache_budget_bytes < 0:
            raise ValueError("cache_budget_bytes must be >= 0")
        if self.checkpoint_every < 0:
            raise ValueError("checkpoint_every must be >= 0")
        if self.max_retries < 0:
            raise ValueError("max_retries must be >= 0")
        if self.retry_backoff < 0:
            raise ValueError("retry_backoff must be >= 0")
        if self.watchdog_seconds < 0:
            raise ValueError("watchdog_seconds must be >= 0")
        if self.checkpoint_keep < 0:
            raise ValueError("checkpoint_keep must be >= 0")
        if self.shard_rows < 1 or self.shard_rows % GROUP_ROWS:
            raise ValueError(f"shard_rows must be a positive multiple of "
                             f"{GROUP_ROWS}, got {self.shard_rows}")
        if self.spill_g and not self.shard_dir:
            raise ValueError("spill_g=True requires shard_dir")
        if self.resume and not self.checkpoint_dir:
            raise ValueError("resume=True requires checkpoint_dir")


def tune_prefetch(h2d_seconds: float, compute_seconds: float, prefetch: int,
                  cap: int = 8) -> int:
    """Minimal overlap-autotune shared by BOTH streamed stages (ROADMAP): the
    in-flight queue hides min(H2D, compute) behind max(H2D, compute) only
    while it is deep enough to keep both sides busy.  When the measured H2D
    time of the first pipeline window exceeds the drain/compute time it is
    supposed to overlap, transfer lags compute — double the queue depth
    (bounded by ``cap``)."""
    if h2d_seconds > compute_seconds and prefetch < cap:
        return min(cap, max(prefetch * 2, prefetch + 1))
    return prefetch


@dataclasses.dataclass
class Stage1StreamStats:
    """Traffic accounting of one streamed stage-1 factor build.

    `bytes_h2d` counts the CHUNK wire bytes (the n-scaling traffic this
    pipeline exists to bound) — int8 scale tables included, broken out in
    `bytes_scales`; the one-time landmark/projector replicas are excluded so
    per-dtype comparisons stay exact."""

    chunks: int = 0
    rows: int = 0
    chunks_skipped: int = 0           # chunks already covered by a resumed
                                      # stage-1 progress log (zero H2D)
    rows_resumed: int = 0             # rows those skipped chunks carried
    rows_skipped: int = 0             # bad ingest rows dropped by the
                                      # on_bad_row="skip" policy upstream
    bytes_h2d: int = 0
    bytes_scales: int = 0
    put_seconds: float = 0.0          # host time inside chunk H2D puts
    drain_seconds: float = 0.0        # host time blocked on G-chunk fetches
    seconds: float = 0.0
    wire_dtype: str = "f32"
    prefetch_final: int = 0           # queue depth after autotune

    @property
    def h2d_gbps(self) -> float:
        """Effective H2D rate over host put time (GB/s)."""
        return self.bytes_h2d / max(self.put_seconds, 1e-12) / 1e9

    @property
    def overlap_efficiency(self) -> float:
        """Stall-free fraction of the wall clock: 1 minus the share spent
        blocked in puts/drains, clamped to [0, 1].  Which of those stalls
        left the device idle is read from a profiler trace of the mirrored
        spans (`core/trace.py`)."""
        if self.seconds <= 0.0:
            return 0.0
        busy = (self.put_seconds + self.drain_seconds) / self.seconds
        return min(1.0, max(0.0, 1.0 - busy))


def resident_bytes(p: int, budget: int) -> int:
    """Device-resident stage-1 state: landmark block + projector."""
    return (budget * p + budget * budget) * BYTES_F32


def chunk_bytes(rows: int, p: int, budget: int) -> int:
    """Working set of ONE in-flight chunk: input rows, K block, G block."""
    return rows * (p + 2 * budget) * BYTES_F32


def monolithic_bytes(n: int, p: int, budget: int) -> int:
    """Device working set of the one-shot path: x, K_nm, G all live at once."""
    return (n * p + 2 * n * budget) * BYTES_F32 + resident_bytes(p, budget)


def should_stream(n: int, p: int, budget: int, cfg: StreamConfig) -> bool:
    """True when the monolithic stage-1 working set blows the device budget."""
    return monolithic_bytes(n, p, budget) > cfg.device_budget_bytes


def auto_chunk_rows(n: int, p: int, budget: int, cfg: StreamConfig) -> int:
    """Largest chunk whose `prefetch` in-flight copies fit the budget.

    Solves  prefetch * chunk_bytes(r) + resident <= device_budget  for r,
    clamped to [min_chunk_rows, n] — the floor keeps tiny budgets from
    degenerating into per-row dispatch (latency-bound), accepting a mild
    budget overshoot instead.
    """
    if cfg.chunk_rows is not None:
        return min(cfg.chunk_rows, n)
    free = cfg.device_budget_bytes - resident_bytes(p, budget)
    per_row = cfg.prefetch * (p + 2 * budget) * BYTES_F32
    rows = free // per_row if free > 0 else 0
    return int(min(n, max(cfg.min_chunk_rows, rows)))


@partial(jax.jit, static_argnames=("params", "gram_fn"))
def _chunk_features(xb, landmarks, projector, params: KernelParams, gram_fn):
    """One chunk's G rows: K(x_chunk, landmarks) @ projector, fused under jit."""
    return jnp.dot(gram_fn(xb, landmarks, params), projector,
                   precision=HIGHEST)


@partial(jax.jit, static_argnames=("params", "group", "gram_q8_fn"))
def _chunk_features_q8(vals, scales, landmarks, projector,
                       params: KernelParams, group: int, gram_q8_fn):
    """One chunk's G rows from the int8 wire: the H2D copy shipped int8
    values + the compact scale table, and the gram kernel dequantises fused
    (no fp32 x chunk ever materialises on device)."""
    return jnp.dot(gram_q8_fn(vals, scales, landmarks, params, group=group),
                   projector, precision=HIGHEST)


def default_gram_q8_fn() -> Callable:
    """Fused-dequant Pallas gram on TPU; the jnp dequant+gram oracle
    elsewhere (interpret-mode Pallas is pure overhead on CPU)."""
    if jax.default_backend() == "tpu":
        from repro.kernels.ops import gram_q8
        return gram_q8
    from repro.kernels.ref import gram_q8_ref
    return gram_q8_ref


def stream_factor_blocks(
    blocks,
    n: int,
    landmarks: jnp.ndarray,
    projector: jnp.ndarray,
    params: KernelParams,
    *,
    prefetch: int = 2,
    gram_fn: Callable = gram,
    out: Optional[np.ndarray] = None,
    devices: Optional[Sequence] = None,
    wire_dtype: str = "f32",
    quant_group_rows: int = GROUP_ROWS,
    gram_q8_fn: Optional[Callable] = None,
    autotune_prefetch: bool = False,
    prefetch_cap: int = 8,
    stats: Optional[Stage1StreamStats] = None,
    trace=None,
    progress=None,
) -> np.ndarray:
    """Fill a host-resident G from an *iterator* of dense row blocks.

    The generic core of `stream_factor_rows`: ``blocks`` yields (rows, p)
    float32 arrays totalling ``n`` rows (e.g. `CSRData.iter_dense_blocks` or
    `read_libsvm_blocks`), so stage 1 never materialises the full dense
    (n, p) host matrix.  Each block is ``jax.device_put`` and the
    gram+project launch dispatched asynchronously, with at most ``prefetch``
    blocks in flight per device before the host blocks on the oldest one and
    copies it into ``out``.  Passing ``devices`` round-robins *disjoint*
    block streams across them (landmarks/projector replicated once per
    device up front).

    ``wire_dtype="int8"`` quantises each chunk host-side with the symmetric
    per-row-group codec (`core/quant.py`; zero padding through the Pallas
    tiles must dequantise to exact zeros, hence symmetric) and ships int8
    values + the compact scale table at ~quarter the H2D bytes; the gram
    consumer (``gram_q8_fn``, `default_gram_q8_fn` when None) fuses the
    dequantisation into its tile loads.

    ``autotune_prefetch`` closes the stage-1 overlap loop (ROADMAP): once
    the first full pipeline window has been measured, the in-flight depth is
    deepened via `tune_prefetch` when H2D put time exceeds drain/compute
    time (bounded by ``prefetch_cap``); the tuned depth lands in
    ``stats.prefetch_final``.

    ``progress`` (a `resilience.Stage1Progress`) makes the stream resumable:
    row ranges already logged as complete are skipped (counted in
    ``stats.chunks_skipped`` / ``rows_resumed``), and every drained chunk is
    durably marked — G flushed before the log line — so a killed stage 1
    restarts at the first missing chunk.
    """
    rank = projector.shape[1]
    if out is None:
        out = np.empty((n, rank), np.float32)
    if out.shape != (n, rank):
        raise ValueError(f"out buffer {out.shape} != {(n, rank)}")
    if devices is None:
        devices = [None]
    if wire_dtype not in ("f32", "int8"):
        raise ValueError(f"stage-1 wire_dtype must be 'f32' or 'int8', "
                         f"got {wire_dtype!r}")
    quant = wire_dtype == "int8"
    if quant and gram_q8_fn is None:
        gram_q8_fn = default_gram_q8_fn()
    st = stats if stats is not None else Stage1StreamStats()
    st.wire_dtype = wire_dtype
    tr = resolve_tracer(trace)
    t_start = time.perf_counter()

    # One resident replica of the landmark block per device.
    resident = []
    for d in devices:
        if d is None:
            resident.append((jnp.asarray(landmarks, jnp.float32),
                             jnp.asarray(projector, jnp.float32)))
        else:
            resident.append((jax.device_put(np.asarray(landmarks, np.float32), d),
                             jax.device_put(np.asarray(projector, np.float32), d)))

    inflight = collections.deque()  # (start, end, device_array)
    g_flush = getattr(out, "flush", None)   # memmap: make marked rows durable

    def drain_one():
        s, e, gb = inflight.popleft()
        t0 = tr.begin("d2h", "stage1_fetch")
        out[s:e] = np.asarray(gb)   # blocks on this chunk only
        st.drain_seconds += tr.end(t0, bytes=int(gb.nbytes), rows=e - s)
        if progress is not None:
            progress.mark(s, e, flush=g_flush)

    def put(a, d):
        t0 = tr.begin("h2d", "stage1_put")
        b = jnp.asarray(a) if d is None else jax.device_put(a, d)
        st.put_seconds += tr.end(t0, bytes=int(a.nbytes))
        st.bytes_h2d += a.nbytes
        return b

    max_inflight = max(1, prefetch) * len(devices)
    tuned = not autotune_prefetch
    s = 0
    for i, xb in enumerate(blocks):
        # Blocks may arrive PRE-ENCODED as `quant.QuantBlock`s (the int8
        # shard store streams its stored codes straight onto the wire —
        # zero re-encode, and bit-equal to the host int8 path because shard
        # scale groups are global-row-aligned).  On the f32 wire they are
        # decoded host-side first.
        pre = isinstance(xb, QuantBlock)
        if pre and not quant:
            xb = dequantize_rows(xb.values, xb.scales, xb.group)
            pre = False
        if not pre:
            xb = np.asarray(xb, np.float32)
        e = s + xb.shape[0]
        if e > n:
            raise ValueError(f"block iterator produced more than {n} rows")
        if progress is not None and progress.covered(s, e):
            # Resumed: this row range is already durably in G — skip the
            # whole put/compute/drain for it (zero H2D).
            st.chunks_skipped += 1
            st.rows_resumed += e - s
            s = e
            continue
        _fault_check("stage1", chunk=i)
        d = devices[i % len(devices)]
        lm, pr = resident[i % len(devices)]
        if quant:
            if pre:
                vals, scales, grp = xb.values, xb.scales, xb.group
            else:
                t0 = tr.begin("encode", "stage1_quant")
                vals, scales = quantize_rows(xb, quant_group_rows,
                                             symmetric=True)
                tr.end(t0, rows=xb.shape[0],
                       bytes=int(vals.nbytes + scales.nbytes))
                grp = quant_group_rows
            st.bytes_scales += scales.nbytes
            bv, bs = put(vals, d), put(scales, d)
            t0 = tr.begin("dispatch", "stage1_chunk")
            gb = _chunk_features_q8(bv, bs, lm, pr,
                                    params, grp, gram_q8_fn)
            tr.end(t0, rows=e - s)
        else:
            bx = put(xb, d)
            t0 = tr.begin("dispatch", "stage1_chunk")
            gb = _chunk_features(bx, lm, pr, params, gram_fn)
            tr.end(t0, rows=e - s)
        st.chunks += 1
        st.rows += e - s
        inflight.append((s, e, gb))
        if len(inflight) >= max_inflight:
            drain_one()
            if not tuned:
                # First pipeline window measured: deepen the in-flight queue
                # if the H2D side could not hide behind the drain/compute.
                tuned = True
                prefetch = tune_prefetch(st.put_seconds, st.drain_seconds,
                                         prefetch, prefetch_cap)
                max_inflight = prefetch * len(devices)
        s = e
    while inflight:
        drain_one()
    if s != n:
        raise ValueError(f"block iterator produced {s} rows, expected {n}")
    st.prefetch_final = prefetch
    st.seconds = time.perf_counter() - t_start
    return out


def stream_factor_rows(
    x,
    landmarks: jnp.ndarray,
    projector: jnp.ndarray,
    params: KernelParams,
    *,
    chunk_rows: int,
    prefetch: int = 2,
    gram_fn: Callable = gram,
    out: Optional[np.ndarray] = None,
    devices: Optional[Sequence] = None,
    **wire_kwargs,
) -> np.ndarray:
    """Fill a host-resident G = K(x, landmarks) @ projector, chunk by chunk.

    ``x`` stays on host (numpy); row chunks of ``chunk_rows`` are sliced off
    it and fed through `stream_factor_blocks`' in-flight pipeline.  Extra
    keyword arguments (``wire_dtype``, ``stats``, ...) pass through.
    """
    x = np.asarray(x, np.float32)
    n = x.shape[0]
    blocks = (x[s:min(s + chunk_rows, n)] for s in range(0, n, chunk_rows))
    return stream_factor_blocks(
        blocks, n, landmarks, projector, params, prefetch=prefetch,
        gram_fn=gram_fn, out=out, devices=devices, **wire_kwargs)


def compute_factor_streamed(
    x,
    params: KernelParams,
    budget: int,
    *,
    key: Optional[jax.Array] = None,
    eig_rtol: Optional[float] = None,
    config: StreamConfig = StreamConfig(),
    gram_fn: Callable = gram,
    devices: Optional[Sequence] = None,
):
    """Out-of-core stage 1: same artifact as `nystrom.compute_factor`, but G
    is a host-resident numpy buffer filled by the chunked pipeline.

    The landmark eigendecomposition is unchanged (B x B fits any device); only
    the (n, B) gram + projection — the part that scales with n — streams.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    x = np.asarray(x, np.float32)
    n, p = x.shape

    if budget >= n:
        landmarks = jnp.asarray(x, jnp.float32)
    else:
        landmarks = jnp.asarray(_select_landmarks_host(x, budget, key),
                                jnp.float32)

    def make_blocks(chunk):
        return (x[s:min(s + chunk, n)] for s in range(0, n, chunk))

    return _streamed_factor_from_landmarks(
        landmarks, make_blocks, n, p, params, eig_rtol=eig_rtol,
        config=config, gram_fn=gram_fn, devices=devices,
        row_provider=lambda s, e: x[s:e])


def compute_factor_streamed_csr(
    data,
    params: KernelParams,
    budget: int,
    *,
    key: Optional[jax.Array] = None,
    eig_rtol: Optional[float] = None,
    config: StreamConfig = StreamConfig(),
    gram_fn: Callable = gram,
    devices: Optional[Sequence] = None,
):
    """Out-of-core stage 1 straight from a `CSRData` (LIBSVM) data set.

    The sparse triple stays the only full-data host object: landmarks are
    gathered row-wise from the CSR storage, and the (n, p) dense matrix is
    only ever materialised one `chunk_rows` block at a time on its way to the
    device (`CSRData.iter_dense_blocks` -> `stream_factor_blocks`).  Uses the
    same landmark permutation as `compute_factor_streamed`, so the factor is
    identical to densify-then-stream for a given key.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    n, p = data.n, data.n_features
    b = min(budget, n)
    if b >= n:
        lm_rows = np.arange(n)
    else:
        lm_rows = np.asarray(jax.random.choice(key, n, shape=(b,),
                                               replace=False))
    landmarks = jnp.asarray(data.densify_rows(lm_rows), jnp.float32)

    def make_blocks(chunk):
        return (blk for blk, _ in data.iter_dense_blocks(chunk))

    return _streamed_factor_from_landmarks(
        landmarks, make_blocks, n, p, params, eig_rtol=eig_rtol,
        config=config, gram_fn=gram_fn, devices=devices,
        row_provider=lambda s, e: data.densify(s, e))


def compute_factor_streamed_shards(
    store,
    params: KernelParams,
    budget: int,
    *,
    key: Optional[jax.Array] = None,
    eig_rtol: Optional[float] = None,
    config: StreamConfig = StreamConfig(),
    gram_fn: Callable = gram,
    devices: Optional[Sequence] = None,
):
    """Out-of-core stage 1 from a checksummed on-disk `shards.ShardStore`.

    The disk-tier twin of `compute_factor_streamed_csr`: the LIBSVM text was
    parsed ONCE into the shard store, and every subsequent epoch/run streams
    the verified binary shards instead of re-parsing.  Each shard is exactly
    one wire chunk (``chunk_rows`` is pinned to the store's ``shard_rows``),
    which keeps two invariants:

      * an f32 store is byte-identical input to the host-RAM stream, so the
        resulting factor is bit-equal to `compute_factor_streamed` on the
        same rows for EVERY stage-1 wire dtype;
      * an int8 store ships its STORED codes straight onto the int8 wire
        (`QuantBlock` pass-through in `stream_factor_blocks` — zero
        re-encode), its global-row-aligned scale groups landing exactly
        where the host quantiser would put them.

    Landmarks are gathered (and for int8 stores, decoded) from the shards
    with the same jax-derived permutation as the other constructors.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    n, p = store.n, store.cols
    b = min(budget, n)
    if b >= n:
        lm_rows = np.arange(n)
    else:
        lm_rows = np.asarray(jax.random.choice(key, n, shape=(b,),
                                               replace=False))
    landmarks = jnp.asarray(store.gather_rows(lm_rows), jnp.float32)

    wire = store.dtype == "int8"

    def make_blocks(chunk):
        return store.iter_blocks(wire=wire)

    def row_provider(s, e):
        if wire:
            return store.read_shard(s // store.shard_rows, wire=True)
        return store.read_rows(s, e)

    cfg = dataclasses.replace(config, chunk_rows=store.shard_rows)
    return _streamed_factor_from_landmarks(
        landmarks, make_blocks, n, p, params, eig_rtol=eig_rtol,
        config=cfg, gram_fn=gram_fn, devices=devices,
        row_provider=row_provider)


def _g_rebuilder(row_provider, chunk: int, n: int, landmarks, projector,
                 params: KernelParams, config: StreamConfig,
                 gram_fn: Callable, devices):
    """Rebuild closure for spilled-G shards: recompute G rows [lo, hi).

    Recomputes whole ORIGINAL chunks (chunk-aligned ranges, same wire dtype
    and quant grouping as the first pass) and slices out the shard — stage-1
    chunks are independent, so the recomputed rows are bit-equal to the
    spilled ones and the shard-digest check in `ShardStore._rebuild` holds.
    """
    def rebuild(lo: int, hi: int) -> np.ndarray:
        c0 = (lo // chunk) * chunk
        c1 = min(n, -(-hi // chunk) * chunk)
        blocks = (row_provider(s, min(s + chunk, c1))
                  for s in range(c0, c1, chunk))
        sub = stream_factor_blocks(
            blocks, c1 - c0, landmarks, projector, params,
            prefetch=config.prefetch, gram_fn=gram_fn, devices=devices,
            wire_dtype=config.stage1_dtype,
            quant_group_rows=config.quant_group_rows,
            autotune_prefetch=False, trace=config.trace)
        return sub[lo - c0:hi - c0]

    return rebuild


def _streamed_factor_from_landmarks(
    landmarks, make_blocks, n: int, p: int, params: KernelParams, *,
    eig_rtol: Optional[float], config: StreamConfig, gram_fn: Callable,
    devices: Optional[Sequence], row_provider=None,
):
    """Shared tail of the streamed stage-1 constructors: eigendecompose the
    landmark kernel, then stream ``make_blocks(chunk_rows)`` into G.

    ``row_provider(s, e)`` re-yields the input rows of [s, e) on demand; it
    is only called when ``config.spill_g`` is set and a spilled G shard
    later fails its checksum (quarantine -> recompute)."""
    from repro.core import nystrom  # deferred: nystrom routes back into us

    if eig_rtol is None:
        eig_rtol = nystrom.DEFAULT_EIG_RTOL
    k_mm = gram_fn(landmarks, landmarks, params)
    projector, evals, rank = nystrom._eig_projector(k_mm, params, eig_rtol)
    rank = int(rank)
    projector = projector[:, :rank]

    chunk = auto_chunk_rows(n, p, landmarks.shape[0], config)
    stats = Stage1StreamStats()
    out = progress = sink = None
    if config.spill_g and config.shard_dir:
        # Disk tier: G streams straight into checksummed f32 shards and is
        # handed to stage 2 as a `GShardView` — the (n, rank) host buffer
        # never exists.  Spill supersedes the stage-1 resume memmap (the
        # shard store IS the durable copy of G).
        import os as _os
        from repro.core.shards import ShardSpillSink
        sink = ShardSpillSink(_os.path.join(config.shard_dir, "g_spill"),
                              n, rank, shard_rows=config.shard_rows,
                              trace=config.trace)
        out = sink
    elif config.checkpoint_dir:
        # Resumable stage 1: G fills an on-disk memmap and completed chunk
        # ranges are logged durably, so a killed run restarts at the first
        # missing chunk.  Landmarks/projector are deterministic from the
        # PRNG key, so the recomputed resident state matches the logged G.
        import os as _os
        from repro.core.resilience import Stage1Progress, stage1_memmap
        out = stage1_memmap(config.checkpoint_dir, n, rank, config.resume)
        progress = Stage1Progress(
            _os.path.join(config.checkpoint_dir, "stage1_progress.log"),
            n, rank, resume=config.resume)
    try:
        G = stream_factor_blocks(
            make_blocks(chunk), n, landmarks, projector, params,
            prefetch=config.prefetch, gram_fn=gram_fn, devices=devices,
            wire_dtype=config.stage1_dtype,
            quant_group_rows=config.quant_group_rows,
            autotune_prefetch=config.autotune_prefetch,
            prefetch_cap=config.prefetch_cap, stats=stats, out=out,
            trace=config.trace, progress=progress)
    finally:
        if progress is not None:
            progress.close()
    if sink is not None:
        rebuilder = None
        if row_provider is not None:
            rebuilder = _g_rebuilder(row_provider, chunk, n, landmarks,
                                     projector, params, config, gram_fn,
                                     devices)
        G = sink.finish(
            rebuilder=rebuilder, verify=config.verify_shards,
            retries=0 if config.fail_fast else config.max_retries,
            retry_backoff=config.retry_backoff)

    return nystrom.LowRankFactor(
        G=G, landmarks=landmarks, projector=projector, eigvals=evals,
        effective_rank=rank, kernel=params, streamed=True,
        stage1_stats=stats)


def _select_landmarks_host(x: np.ndarray, budget: int, key) -> np.ndarray:
    """Landmark sample without shipping the full x to device first.

    `nystrom.select_landmarks` takes device-resident x; at out-of-core scale
    that defeats the purpose, so gather the B rows on host from the same
    jax-derived permutation (bit-identical landmark set for a given key).
    """
    idx = np.asarray(jax.random.choice(key, x.shape[0], shape=(budget,),
                                       replace=False))
    return x[idx]
