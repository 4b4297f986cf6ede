"""Checkpoint/resume + graceful degradation for the streamed solvers.

Three pieces, all keyed by GLOBAL task index so the saved state is
device-count independent (per-task trajectories are numerically independent
of tile size and device placement — PR 7's task-LOCAL coordinates):

* `snapshot_engines` / `restore_engines` — serialise the full stage-2 solver
  state of one or more `_Stage2Engine`s at a FULL-PASS epoch boundary
  (alpha/unchanged/w per task, ladder lifecycle flags, convergence counters,
  merged stream-stats carry) into a flat tree for `repro.checkpoint`'s
  msgpack format, and restore it onto freshly built engines — possibly split
  over a DIFFERENT device count.  Restores re-run the engine's shrinking
  re-compaction (a pure function of the restored unchanged-counters), so a
  resumed run replays the uninterrupted trajectory bit-for-bit.

* `StreamGuard` — the driver-side policy object: writes a disk checkpoint
  every `checkpoint_every` full passes, keeps the last epoch-boundary
  snapshot in memory when graceful degradation is on (`fail_fast=False`), and
  carries the already-accounted stream stats across resume segments so the
  merged record matches an uninterrupted run.

* `Stage1Progress` — resumable stage-1 factor streaming: G fills an on-disk
  memmap and every drained chunk appends its row range to an append-only log
  (data flushed before the log line, so logged ranges are durable); a
  restarted stage 1 skips the covered chunks.

Snapshots happen ONLY at full-pass boundaries: the engine's compaction state
is a pure function of post-full-pass state, so it is recomputed at restore
instead of serialised, and a failure mid-cheap-epoch rolls back to the last
full pass and replays deterministically.
"""
from __future__ import annotations

import dataclasses
import os
import re
import time
from typing import Dict, List, Optional, Sequence

import msgpack
import numpy as np

from repro.checkpoint.ckpt import latest_step, save_checkpoint
from repro.core.faults import classify_error  # noqa: F401  (re-export: the
#   real recovery taxonomy lives with the injectable faults)


class WatchdogTimeout(RuntimeError):
    """The farm barrier starved past `StreamConfig.watchdog_seconds` — raised
    with queue/thread diagnostics instead of hanging forever."""


class WorkerStuckError(RuntimeError):
    """`_DeviceWorkers.close()` found a worker thread still alive after its
    join timeout (previously a silent leak)."""


# ---------------------------------------------------------------------------
# stream-stats carry: the already-accounted counters of previous segments
# ---------------------------------------------------------------------------

_CARRY_SUM = ("bytes_h2d", "bytes_d2h", "bytes_g", "bytes_scales",
              "bytes_put", "bytes_hit", "bytes_miss", "blocks_streamed",
              "rows_streamed", "kernel_calls", "coord_visits", "cache_hits",
              "cache_misses", "cache_evictions", "cache_resident_bytes",
              "full_passes", "h2d_puts", "d2h_syncs")
_CARRY_SUM_F = ("put_seconds", "drain_seconds", "seconds")
_CARRY_MAX = ("epochs", "prefetch_final")
_CARRY_LIST = ("epoch_bytes", "epoch_hit_bytes", "epoch_miss_bytes",
               "active_history")


def stats_to_carry(stats) -> Dict[str, np.ndarray]:
    """Flatten the carry-relevant fields of a `Stage2StreamStats`."""
    out: Dict[str, np.ndarray] = {}
    for f in _CARRY_SUM + _CARRY_MAX:
        out[f] = np.asarray(getattr(stats, f), np.int64)
    for f in _CARRY_SUM_F:
        out[f] = np.asarray(getattr(stats, f), np.float64)
    for f in _CARRY_LIST:
        out[f] = np.asarray(getattr(stats, f), np.int64)
    return out


def add_carry(carry: Dict[str, np.ndarray],
              base: Optional[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    """Fold an EARLIER segment's carry (``base``) under ``carry``: counters
    sum, high-water marks max, per-epoch lists concatenate (base first)."""
    if base is None:
        return carry
    out = dict(carry)
    for f in _CARRY_SUM:
        out[f] = np.asarray(int(carry[f]) + int(base[f]), np.int64)
    for f in _CARRY_SUM_F:
        out[f] = np.asarray(float(carry[f]) + float(base[f]), np.float64)
    for f in _CARRY_MAX:
        out[f] = np.asarray(max(int(carry[f]), int(base[f])), np.int64)
    for f in _CARRY_LIST:
        out[f] = np.concatenate([np.asarray(base[f], np.int64),
                                 np.asarray(carry[f], np.int64)])
    return out


def apply_carry(stats, carry: Optional[Dict[str, np.ndarray]]):
    """Fold a carry tree into a freshly merged `Stage2StreamStats` (the
    resumed segment): the result reads like one uninterrupted run."""
    if carry is None:
        return stats
    for f in _CARRY_SUM:
        setattr(stats, f, getattr(stats, f) + int(carry[f]))
    for f in _CARRY_SUM_F:
        setattr(stats, f, getattr(stats, f) + float(carry[f]))
    for f in _CARRY_MAX:
        setattr(stats, f, max(getattr(stats, f), int(carry[f])))
    for f in _CARRY_LIST:
        setattr(stats, f, [int(v) for v in carry[f]] + getattr(stats, f))
    return stats


# ---------------------------------------------------------------------------
# stage-2 snapshot / restore (global-task-keyed)
# ---------------------------------------------------------------------------

def g_fingerprint(G) -> float:
    """Cheap content stamp of the factor (guards resuming onto the wrong G,
    e.g. another gamma's checkpoint directory).

    A shard-backed G (`shards.GShardView`) publishes its own fingerprint,
    derived from the store manifest's per-shard digests — so snapshots
    record the shard-manifest identity and ``resume`` refuses to continue
    against a store that was re-ingested or otherwise mutated, without
    reading a single row back from disk."""
    fp = getattr(G, "g_fingerprint", None)
    if fp is not None:
        return float(fp)
    n = G.shape[0]
    if n == 0:
        return 0.0
    return float(np.float64(G[0].sum()) + np.float64(G[-1].sum())
                 + np.float64(n) * G.shape[1])


def snapshot_engines(engines: Sequence, sizes: np.ndarray, *,
                     epoch_next: int, init_done: bool,
                     carry: Dict[str, np.ndarray], n: int, rank: int,
                     g_fp: float) -> Dict:
    """Serialise the engines' solver state into a global-task-keyed tree.

    ``sizes[g]`` is global task g's real-row count; per-task alpha/unchanged
    are concatenated in global task order.  w is fetched D2H here — it is
    device-resident incremental float state, so bit-parity REQUIRES saving it
    rather than recomputing it from alpha.
    """
    sizes = np.asarray(sizes, np.int64)
    T = len(sizes)
    off = np.concatenate([np.zeros(1, np.int64), np.cumsum(sizes)])
    a_cat = np.zeros(int(off[-1]), np.float32)
    u_cat = np.zeros(int(off[-1]), np.int32)
    w = np.zeros((T, rank), np.float32)
    done = np.zeros(T, np.uint8)
    violation = np.zeros(T, np.float32)
    epochs_used = np.zeros(T, np.int32)
    first_sweep = np.zeros(T, np.int32)
    active = np.zeros(T, np.uint8)
    pending = np.zeros(T, np.uint8)
    epochs_run = 0
    for e in engines:
        pend = set(e.pending_init)
        for t in range(e.T):
            g = int(e.task_ids[t])
            s0, s1 = int(off[g]), int(off[g + 1])
            if s1 - s0 != len(e.a_r[t]):
                raise ValueError(f"task {g}: snapshot size {s1 - s0} != "
                                 f"engine rows {len(e.a_r[t])}")
            a_cat[s0:s1] = e.a_r[t]
            u_cat[s0:s1] = e.u_r[t]
            w[g] = np.asarray(e.w[t])
            done[g] = e.done[t]
            violation[g] = e.violation[t]
            epochs_used[g] = e.epochs_used[t]
            first_sweep[g] = e.first_sweep[t]
            active[g] = e.active[t]
            pending[g] = t in pend
        epochs_run = max(epochs_run, e.epochs_run)
    return {
        "meta": {
            "epoch_next": np.asarray(epoch_next, np.int64),
            "init_done": np.asarray(int(init_done), np.int64),
            "epochs_run": np.asarray(epochs_run, np.int64),
            "n": np.asarray(n, np.int64),
            "rank": np.asarray(rank, np.int64),
            "T": np.asarray(T, np.int64),
            "g_fp": np.asarray(g_fp, np.float64),
        },
        "sizes": sizes,
        "a": a_cat, "u": u_cat, "w": w,
        "done": done, "violation": violation, "epochs_used": epochs_used,
        "first_sweep": first_sweep, "active": active, "pending": pending,
        "stats": carry,
    }


def restore_engines(engines: Sequence, snap: Dict) -> None:
    """Restore a snapshot onto freshly built engines (any device split that
    partitions the same global task set).  Re-runs each engine's shrinking
    re-compaction (`_recompact(record=False)`) so the compacted cheap-epoch
    state matches what the uninterrupted run had after the boundary's full
    pass — without double-appending its stats/history records."""
    from repro.core.solver_stream import _put

    sizes = np.asarray(snap["sizes"], np.int64)
    off = np.concatenate([np.zeros(1, np.int64), np.cumsum(sizes)])
    epochs_run = int(snap["meta"]["epochs_run"])
    for e in engines:
        pending: List[int] = []
        for t in range(e.T):
            g = int(e.task_ids[t])
            s0, s1 = int(off[g]), int(off[g + 1])
            if s1 - s0 != len(e.a_r[t]):
                raise ValueError(f"task {g}: checkpoint rows {s1 - s0} != "
                                 f"engine rows {len(e.a_r[t])}")
            e.a_r[t][:] = snap["a"][s0:s1]
            e.u_r[t][:] = snap["u"][s0:s1]
            e.w[t] = _put(np.ascontiguousarray(snap["w"][g], np.float32),
                          e.device)
            e.done[t] = bool(snap["done"][g])
            e.violation[t] = snap["violation"][g]
            e.epochs_used[t] = snap["epochs_used"][g]
            e.first_sweep[t] = snap["first_sweep"][g]
            e.active[t] = bool(snap["active"][g])
            if snap["pending"][g]:
                pending.append(t)
        e.pending_init = pending
        e.epochs_run = epochs_run
        e._epoch = epochs_run - 1
        e._recompact(record=False)


def validate_snapshot(snap: Dict, *, n: int, rank: int, sizes,
                      g_fp: float) -> None:
    meta = snap["meta"]
    if int(meta["n"]) != n or int(meta["rank"]) != rank:
        raise ValueError(
            f"checkpoint shape mismatch: saved (n={int(meta['n'])}, "
            f"rank={int(meta['rank'])}), solve has (n={n}, rank={rank})")
    sizes = np.asarray(sizes, np.int64)
    if int(meta["T"]) != len(sizes) or not np.array_equal(
            np.asarray(snap["sizes"], np.int64), sizes):
        raise ValueError("checkpoint task structure does not match this solve")
    if abs(float(meta["g_fp"]) - g_fp) > 1e-6 * max(1.0, abs(g_fp)):
        raise ValueError("checkpoint factor fingerprint does not match G — "
                         "resuming against a different factor?")


def load_snapshot(directory: str, step: Optional[int] = None) -> Optional[Dict]:
    """Load a stage-2 snapshot written by `StreamGuard` (latest step when
    ``step`` is None).  Template-free: snapshot trees hold variable-length
    per-epoch lists, so shapes come from the file itself."""
    if step is None:
        step = latest_step(directory)
        if step is None:
            return None
    path = os.path.join(directory, f"step_{step:08d}.msgpack")
    with open(path, "rb") as f:
        payload = msgpack.unpackb(f.read(), raw=False)
    out: Dict = {}
    for key, rec in payload.items():
        arr = np.frombuffer(rec["data"], dtype=np.dtype(rec["dtype"]))
        arr = arr.reshape(rec["shape"]).copy()
        node = out
        parts = key.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = arr
    return out


# ---------------------------------------------------------------------------
# the driver-side guard
# ---------------------------------------------------------------------------

class StreamGuard:
    """Policy + state for checkpointing and degradation of ONE streamed
    stage-2 solve.  The driver calls `on_start` / `mark_init` /
    `on_boundary`; the solve entry points call `try_resume` and read
    `start_epoch` / `carry`."""

    def __init__(self, cfg, *, n: int, rank: int, sizes, g_fp: float,
                 degrade: bool = False):
        self.cfg = cfg
        self.dir = cfg.checkpoint_dir
        self.every = cfg.checkpoint_every if self.dir else 0
        self.degrade = degrade
        self.n, self.rank, self.g_fp = n, rank, g_fp
        self.sizes = np.asarray(sizes, np.int64)
        self.start_epoch = 0
        self.init_done = False
        self.carry: Optional[Dict[str, np.ndarray]] = None
        self.mem: Optional[Dict] = None    # last epoch-boundary snapshot
        self.saved_steps: List[int] = []
        self._fulls = 0
        self._t0 = time.perf_counter()

    # -- resume -------------------------------------------------------------
    def try_resume(self) -> Optional[Dict]:
        if not self.dir:
            return None
        snap = load_snapshot(self.dir)
        if snap is None:
            return None
        validate_snapshot(snap, n=self.n, rank=self.rank, sizes=self.sizes,
                          g_fp=self.g_fp)
        return snap

    def adopt(self, snap: Dict) -> None:
        """Continue from ``snap``: the next driver segment starts at its
        epoch boundary and the already-accounted stats ride `carry`."""
        self.mem = snap
        self.start_epoch = int(snap["meta"]["epoch_next"])
        self.init_done = bool(int(snap["meta"]["init_done"]))
        self.carry = snap.get("stats")
        self._t0 = time.perf_counter()

    def adopt_mem(self) -> None:
        if self.mem is None:
            raise RuntimeError("no epoch-boundary snapshot to degrade from")
        self.adopt(self.mem)

    # -- driver hooks -------------------------------------------------------
    def _snapshot(self, engines, reader, epoch_next: int) -> Dict:
        from repro.core.solver_stream import merge_stream_stats
        cur = merge_stream_stats(reader, [e.stats for e in engines],
                                 seconds=time.perf_counter() - self._t0,
                                 n_devices=len(engines))
        cur.epochs = max((e.epochs_run for e in engines), default=0)
        cur.prefetch_final = max((e.pipe.prefetch for e in engines), default=0)
        carry = add_carry(stats_to_carry(cur), self.carry)
        return snapshot_engines(engines, self.sizes, epoch_next=epoch_next,
                                init_done=self.init_done, carry=carry,
                                n=self.n, rank=self.rank, g_fp=self.g_fp)

    def on_start(self, engines, reader) -> None:
        """Before the init pass: seed the in-memory degradation snapshot so a
        failure before the first boundary can still re-shard."""
        if self.degrade and self.mem is None:
            self.mem = self._snapshot(engines, reader, self.start_epoch)

    def mark_init(self, engines, reader) -> None:
        self.init_done = True
        if self.degrade:
            self.mem = self._snapshot(engines, reader, self.start_epoch)

    def on_boundary(self, engines, reader, epoch: int, trace=None) -> None:
        """After `finish_epoch` of a FULL-pass epoch — the only state the
        snapshot format covers (compaction is recomputed at restore)."""
        self._fulls += 1
        snap = None
        if self.every and self._fulls % self.every == 0:
            snap = self._snapshot(engines, reader, epoch + 1)
            save_checkpoint(self.dir, epoch + 1, snap)
            self.saved_steps.append(epoch + 1)
            if trace is not None:
                trace.instant("recovery", "checkpoint", epoch=epoch,
                              step=epoch + 1)
            self._prune()
        if self.degrade:
            self.mem = snap if snap is not None else self._snapshot(
                engines, reader, epoch + 1)

    def _prune(self) -> None:
        """Keep-last-k snapshot retention (``cfg.checkpoint_keep``, 0 = keep
        everything).  Strictly delete-AFTER-write: pruning runs only once
        the new snapshot has atomically landed, and deletes ascending from
        the oldest — a crash mid-prune can never remove the newest good
        snapshot, only leave extra old ones behind."""
        keep = int(getattr(self.cfg, "checkpoint_keep", 0))
        if keep <= 0 or not self.dir:
            return
        steps = sorted(int(m.group(1)) for f in os.listdir(self.dir)
                       if (m := re.match(r"step_(\d+)\.msgpack$", f)))
        for s in steps[:-keep]:
            try:
                os.remove(os.path.join(self.dir, f"step_{s:08d}.msgpack"))
            except OSError:
                pass


# ---------------------------------------------------------------------------
# resumable stage-1 factor streaming
# ---------------------------------------------------------------------------

class Stage1Progress:
    """Append-only row-range log of completed stage-1 chunks.

    Each drained chunk calls `mark(s, e, flush)`: the G memmap is flushed
    FIRST, then the "s e" line is written and fsync'd — so every logged range
    is durably in the G file, and a killed stage 1 restarts at the first
    missing chunk.  The log header pins (n, rank); a mismatch (different
    data/kernel/budget) invalidates the log and streaming restarts clean.
    """

    def __init__(self, path: str, n: int, rank: int, resume: bool = True):
        self.path = path
        self.n, self.rank = n, rank
        self._ranges: List = []
        header = f"{n} {rank}"
        if os.path.exists(path):
            keep = False
            if resume:
                with open(path, "r") as f:
                    lines = [ln.strip() for ln in f if ln.strip()]
                if lines and lines[0] == header:
                    keep = True
                    for ln in lines[1:]:
                        s, e = ln.split()
                        self._ranges.append((int(s), int(e)))
            if not keep:
                os.remove(path)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        fresh = not os.path.exists(self.path)
        self._f = open(self.path, "a")
        if fresh:
            self._f.write(header + "\n")
            self._f.flush()
            os.fsync(self._f.fileno())

    @property
    def rows_done(self) -> int:
        return sum(e - s for s, e in self._ranges)

    def covered(self, s: int, e: int) -> bool:
        return any(rs <= s and e <= re for rs, re in self._ranges)

    def mark(self, s: int, e: int, flush=None) -> None:
        if flush is not None:
            flush()
        self._f.write(f"{s} {e}\n")
        self._f.flush()
        os.fsync(self._f.fileno())
        self._ranges.append((s, e))

    def close(self) -> None:
        self._f.close()


def stage1_memmap(directory: str, n: int, rank: int,
                  resume: bool) -> np.ndarray:
    """The host-resident G as an on-disk memmap under the checkpoint dir, so
    completed chunk ranges survive a kill.  A shape/dtype mismatch (or
    ``resume=False``) recreates it."""
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, "stage1_G.npy")
    if resume and os.path.exists(path):
        try:
            out = np.lib.format.open_memmap(path, mode="r+")
            if out.shape == (n, rank) and out.dtype == np.float32:
                return out
        except (ValueError, OSError):
            pass
    return np.lib.format.open_memmap(path, mode="w+", dtype=np.float32,
                                     shape=(n, rank))
