"""Durable disk tier: the checksummed binary shard store.

The paper's "more RAM!" argument is a memory hierarchy; this module extends
it one tier below host RAM, so LIBSVM text is parsed ONCE into binary shards
and datasets (or a spilled stage-1 factor G) larger than host memory
re-stream per epoch from NVMe through the existing
`stream_factor_blocks` / `iter_shared_blocks` pipelines.  A disk tier that
training trusts blindly is a liability on day-long runs, so the store is
built robustness-first:

  * **Every write is atomic** — shard files and the manifest are written to
    a temp file, fsynced, then `os.replace`d into place, and the manifest is
    written LAST.  A kill -9 at ANY point leaves either a fully valid store
    or no manifest (never a readable-but-wrong shard behind a valid
    manifest).
  * **Every read is verified** — each shard carries an xxhash64 (CRC32
    fallback) digest over its header+payload in a fixed footer, and the
    manifest pins every shard's expected digest plus a whole-store
    fingerprint.  Torn writes, bit rot, and stale files are all detected on
    the first read, not silently trained on.
  * **Corruption is recoverable** — a checksum mismatch quarantines the bad
    file under ``quarantine/`` and, when a ``rebuilder`` is attached,
    regenerates the shard from source (re-parse that LIBSVM row range, or
    recompute the G rows) and verifies the rebuild reproduces the
    manifest's digest bit-exactly.  Transient IO errors retry with the
    same bounded-backoff taxonomy as the H2D path (`faults.classify_error`).
  * **Everything is injectable** — deterministic `FaultSpec` sites
    (``shard_write``, ``shard_read``, ``shard_corrupt`` — an in-place
    bit-flip) make the whole recovery surface testable with zero wall-clock
    randomness (`tests/test_shards.py`).

Shard file layout (fixed offsets, so a verified file is memory-mappable)::

    [0:64)    header: magic "LPDSHRD1", version, dtype code, rows, cols,
              group, section byte counts (values / scales / labels)
    [64:...)  values   rows*cols of f32 or int8
              scales   (ng, 2) f32 per-group (scale, zero), int8 shards only
              labels   (rows,) f64, dataset shards only
    [-8:]     footer: u64 digest of header+payload

int8 shards use the symmetric `core/quant.py` codec with scale groups
aligned to the shard start; because ``shard_rows`` is a multiple of
`GROUP_ROWS`, every group boundary is GLOBAL-row-aligned — the same
alignment contract the streamed stage-2 wire relies on, so a shard-resident
G serves `group_scales` tables identical to a host-resident G's.
"""
from __future__ import annotations

import dataclasses
import json
import os
import struct
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, Iterator, List, Optional, Tuple, Union

import numpy as np

from repro.core.faults import check as _fault_check
from repro.core.faults import classify_error
from repro.core.quant import (GROUP_ROWS, QuantBlock, dequantize_rows,
                              dequantize_rows_range,
                              group_scales as quant_group_scales,
                              quantize_rows)
from repro.core.trace import resolve as resolve_tracer

try:
    import xxhash as _xxhash
    HASH_NAME = "xxh64"
except ImportError:                                   # pragma: no cover
    _xxhash = None
    HASH_NAME = "crc32"

MAGIC = b"LPDSHRD1"
VERSION = 1
MANIFEST_NAME = "manifest.json"
QUARANTINE_DIR = "quarantine"
#: magic(8) version(u32) dtype(u32) rows cols group values scales labels (u64)
_HEADER = struct.Struct("<8sIIQQQQQQ")
_FOOTER = struct.Struct("<Q")
HEADER_BYTES = _HEADER.size
FOOTER_BYTES = _FOOTER.size
_DTYPE_CODES = {"f32": 0, "int8": 1}
_DTYPE_NAMES = {v: k for k, v in _DTYPE_CODES.items()}
SHARD_DTYPES = tuple(_DTYPE_CODES)


class ShardError(Exception):
    """Structural problem with a shard store (missing manifest, bad layout,
    a rebuild that failed to reproduce the manifest digest, ...)."""


class ShardCorruptionError(ShardError):
    """A shard's bytes do not match its recorded digest (bit rot, torn or
    foreign file) and no rebuilder could restore it."""


@dataclasses.dataclass
class ShardStoreStats:
    """Counters of one store's disk traffic and recovery activity."""

    shards_written: int = 0
    shards_read: int = 0
    bytes_written: int = 0
    bytes_read: int = 0
    verifications: int = 0        # checksum computations on read
    checksum_failures: int = 0    # reads whose digest did not match
    quarantined: int = 0          # corrupt files moved to quarantine/
    rebuilt: int = 0              # shards regenerated from source
    retries: int = 0              # transient-IO read retries
    read_seconds: float = 0.0
    write_seconds: float = 0.0

    @property
    def read_gbps(self) -> float:
        return self.bytes_read / max(self.read_seconds, 1e-12) / 1e9


def shard_name(i: int) -> str:
    return f"shard_{i:05d}.bin"


class _Crc32Hasher:
    """8-byte-digest stand-in when xxhash is absent (stdlib zlib.crc32)."""

    def __init__(self):
        import zlib
        self._crc32 = zlib.crc32
        self._state = 0
        self._length = 0

    def update(self, buf) -> None:
        self._state = self._crc32(buf, self._state)
        self._length = (self._length + len(buf)) & 0xFFFFFFFF

    def intdigest(self) -> int:
        return (self._state << 32) | self._length


def _hasher():
    return _xxhash.xxh64() if _xxhash is not None else _Crc32Hasher()


def _digest(buffers) -> int:
    h = _hasher()
    for b in buffers:
        h.update(b)
    return h.intdigest()


def _fsync_write(path: str, buffers) -> int:
    """Temp-file + fsync + atomic-rename write; returns bytes written."""
    tmp = f"{path}.tmp.{os.getpid()}"
    nbytes = 0
    with open(tmp, "wb") as f:
        for b in buffers:
            f.write(b)
            nbytes += len(b)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return nbytes


def _pack_shard(values: np.ndarray, scales: Optional[np.ndarray],
                labels: Optional[np.ndarray], dtype: str,
                group: int) -> Tuple[List[bytes], int]:
    """Serialise one shard to (buffers, digest); buffers end with the footer."""
    vb = np.ascontiguousarray(values).tobytes()
    sb = (np.ascontiguousarray(scales, np.float32).tobytes()
          if scales is not None else b"")
    lb = (np.ascontiguousarray(labels, np.float64).tobytes()
          if labels is not None else b"")
    header = _HEADER.pack(MAGIC, VERSION, _DTYPE_CODES[dtype],
                          values.shape[0], values.shape[1], group,
                          len(vb), len(sb), len(lb))
    digest = _digest((header, vb, sb, lb))
    return [header, vb, sb, lb, _FOOTER.pack(digest)], digest


def _parse_shard(buf: bytes, path: str, *, verify: bool) -> Dict[str, object]:
    """Decode one shard file's bytes; raise `ShardCorruptionError` on any
    structural or digest mismatch (never return partially-trusted data)."""
    if len(buf) < HEADER_BYTES + FOOTER_BYTES:
        raise ShardCorruptionError(f"{path}: truncated ({len(buf)} bytes)")
    magic, version, code, rows, cols, group, nv, ns, nl = \
        _HEADER.unpack_from(buf)
    if magic != MAGIC or version != VERSION or code not in _DTYPE_NAMES:
        raise ShardCorruptionError(f"{path}: bad shard header")
    if len(buf) != HEADER_BYTES + nv + ns + nl + FOOTER_BYTES:
        raise ShardCorruptionError(
            f"{path}: size {len(buf)} does not match header sections")
    payload_end = HEADER_BYTES + nv + ns + nl
    if verify:
        (expect,) = _FOOTER.unpack_from(buf, payload_end)
        if _digest((buf[:payload_end],)) != expect:
            raise ShardCorruptionError(f"{path}: checksum mismatch")
    dtype = _DTYPE_NAMES[code]
    o = HEADER_BYTES
    values = np.frombuffer(buf, np.int8 if dtype == "int8" else np.float32,
                           count=rows * cols, offset=o).reshape(rows, cols)
    o += nv
    scales = (np.frombuffer(buf, np.float32, count=ns // 4, offset=o)
              .reshape(-1, 2) if ns else None)
    o += ns
    labels = (np.frombuffer(buf, np.float64, count=nl // 8, offset=o)
              if nl else None)
    return dict(values=values, scales=scales, labels=labels, rows=int(rows),
                cols=int(cols), dtype=dtype, group=int(group))


def source_fingerprint(path: str) -> Dict[str, object]:
    """Cheap content identity of an ingest source: size + head/tail digest.

    Deliberately mtime-free so copying the file around does not invalidate
    the shard store; a content edit anywhere near either end (LIBSVM appends
    and truncations included) changes it."""
    size = os.path.getsize(path)
    h = _hasher()
    with open(path, "rb") as f:
        h.update(f.read(1 << 20))
        if size > (1 << 20):
            f.seek(max(size - (1 << 20), 1 << 20))
            h.update(f.read(1 << 20))
    return {"size": int(size), "digest": f"{h.intdigest():016x}"}


class ShardWriter:
    """Buffers rows and emits fixed-size, checksummed shard files.

    All shards except the last hold exactly ``shard_rows`` rows, so shard i
    covers global rows [i*shard_rows, (i+1)*shard_rows) — the fixed
    row-block layout the (tile, B) staging paths rely on.  `finish` writes
    the manifest LAST (atomically): until it lands, the store does not exist
    as far as readers are concerned.
    """

    def __init__(self, directory: str, cols: int, *, shard_rows: int = 4096,
                 dtype: str = "f32", group: int = GROUP_ROWS,
                 kind: str = "dataset", with_labels: bool = False,
                 source: Optional[Dict[str, object]] = None,
                 extra: Optional[Dict[str, object]] = None,
                 stats: Optional[ShardStoreStats] = None, trace=None):
        if dtype not in _DTYPE_CODES:
            raise ValueError(f"shard dtype must be one of {SHARD_DTYPES}, "
                             f"got {dtype!r}")
        if shard_rows < 1 or shard_rows % GROUP_ROWS:
            # multiples of GROUP_ROWS keep int8 scale groups (and any future
            # re-encode of the same rows) global-row-aligned at shard starts
            raise ValueError(f"shard_rows must be a positive multiple of "
                             f"{GROUP_ROWS}, got {shard_rows}")
        self.directory = directory
        self.cols = int(cols)
        self.shard_rows = int(shard_rows)
        self.dtype = dtype
        self.group = int(group)
        self.kind = kind
        self.with_labels = with_labels
        self.source = source
        self.extra = dict(extra or {})
        self.stats = stats if stats is not None else ShardStoreStats()
        self.trace = resolve_tracer(trace)
        self._pending: List[np.ndarray] = []
        self._pending_labels: List[np.ndarray] = []
        self._buffered = 0
        self._shards: List[Dict[str, object]] = []
        self._n = 0
        self._finished = False
        os.makedirs(directory, exist_ok=True)
        # a re-ingest must never leave the OLD manifest validating NEW
        # shards: drop it before the first byte is rewritten
        try:
            os.remove(os.path.join(directory, MANIFEST_NAME))
        except FileNotFoundError:
            pass

    def append(self, rows: np.ndarray,
               labels: Optional[np.ndarray] = None) -> None:
        rows = np.asarray(rows, np.float32)
        if rows.ndim != 2 or rows.shape[1] != self.cols:
            raise ValueError(f"expected (r, {self.cols}) rows, "
                             f"got {rows.shape}")
        if self.with_labels:
            if labels is None or len(labels) != rows.shape[0]:
                raise ValueError("labels must accompany every row")
            self._pending_labels.append(np.asarray(labels, np.float64))
        self._pending.append(rows)
        self._buffered += rows.shape[0]
        while self._buffered >= self.shard_rows:
            self._emit(self.shard_rows)

    def _take(self, count: int) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        rows = np.concatenate(self._pending) if len(self._pending) > 1 \
            else self._pending[0]
        labels = None
        if self.with_labels:
            labels = (np.concatenate(self._pending_labels)
                      if len(self._pending_labels) > 1
                      else self._pending_labels[0])
            self._pending_labels = ([labels[count:]]
                                    if count < len(labels) else [])
            labels = labels[:count]
        self._pending = [rows[count:]] if count < rows.shape[0] else []
        self._buffered -= count
        return rows[:count], labels

    def _emit(self, count: int) -> None:
        block, labels = self._take(count)
        i = len(self._shards)
        _fault_check("shard_write", shard=i)
        if self.dtype == "int8":
            values, scales = quantize_rows(block, self.group, symmetric=True)
        else:
            values, scales = block, None
        buffers, digest = _pack_shard(values, scales, labels, self.dtype,
                                      self.group)
        path = os.path.join(self.directory, shard_name(i))
        t0 = self.trace.begin("disk", "shard_write")
        nbytes = _fsync_write(path, buffers)
        self.stats.write_seconds += self.trace.end(t0, shard=i,
                                                   bytes=nbytes)
        self.stats.shards_written += 1
        self.stats.bytes_written += nbytes
        self._shards.append({"name": shard_name(i), "rows": int(count),
                             "digest": f"{digest:016x}",
                             "nbytes": int(nbytes)})
        self._n += count

    def finish(self) -> Dict[str, object]:
        """Flush the tail shard and atomically publish the manifest."""
        if self._finished:
            raise ShardError("ShardWriter.finish called twice")
        if self._buffered:
            self._emit(self._buffered)
        self._finished = True
        manifest = {
            "version": VERSION, "kind": self.kind, "hash": HASH_NAME,
            "n": int(self._n), "cols": self.cols,
            "shard_rows": self.shard_rows, "dtype": self.dtype,
            "group": self.group, "labels": self.with_labels,
            "shards": self._shards,
            "fingerprint": store_fingerprint(
                self._n, self.cols, self.dtype, self._shards),
        }
        if self.source is not None:
            manifest["source"] = self.source
        manifest.update(self.extra)
        _fsync_write(os.path.join(self.directory, MANIFEST_NAME),
                     [json.dumps(manifest, indent=1).encode()])
        # drop stale shard files from a previous, larger store in the same
        # directory (they are unreachable once the new manifest landed)
        for f in os.listdir(self.directory):
            if f.startswith("shard_") and f.endswith(".bin") \
                    and f not in {s["name"] for s in self._shards}:
                try:
                    os.remove(os.path.join(self.directory, f))
                except OSError:
                    pass
        return manifest


def store_fingerprint(n: int, cols: int, dtype: str,
                      shards: List[Dict[str, object]]) -> str:
    """Whole-store identity: digest of the dims + every shard's digest.

    Any mutation — different data, re-ingest with other params, a rebuilt
    store — changes it; `resilience.validate_snapshot` compares it (through
    `GShardView.g_fingerprint`) so ``--resume`` refuses a mutated store."""
    h = _hasher()
    h.update(f"{n}:{cols}:{dtype}".encode())
    for s in shards:
        h.update(str(s["digest"]).encode())
    return f"{h.intdigest():016x}"


class ShardStore:
    """Verified reader over a shard directory written by `ShardWriter`.

    Every disk read recomputes the footer digest (``verify=True``), retries
    transient IO errors with bounded exponential backoff (``retries`` /
    ``retry_backoff``; fail-fast callers pass ``retries=0``), and routes
    digest mismatches through quarantine + rebuild when a ``rebuilder`` —
    ``(lo, hi) -> (rows f32[, labels])`` over global row range — is
    attached.  Thread-safe: stage-2 farm engines gather rows concurrently.
    """

    def __init__(self, directory: str, *, verify: bool = True,
                 retries: int = 0, retry_backoff: float = 0.05,
                 rebuilder: Optional[Callable] = None,
                 cache_shards: int = 2,
                 stats: Optional[ShardStoreStats] = None, trace=None):
        self.directory = directory
        self.verify = verify
        self.retries = int(retries)
        self.retry_backoff = float(retry_backoff)
        self.rebuilder = rebuilder
        self.stats = stats if stats is not None else ShardStoreStats()
        self.trace = resolve_tracer(trace)
        self._lock = threading.RLock()
        self._cache: "OrderedDict[int, np.ndarray]" = OrderedDict()
        self._cache_shards = max(0, int(cache_shards))
        self._labels: Optional[np.ndarray] = None
        mpath = os.path.join(directory, MANIFEST_NAME)
        try:
            with open(mpath) as f:
                manifest = json.load(f)
        except FileNotFoundError:
            raise ShardError(
                f"no shard manifest at {mpath} — the store was never "
                f"completed (interrupted ingest?); re-ingest to rebuild it")
        except (OSError, json.JSONDecodeError) as exc:
            raise ShardError(f"unreadable shard manifest at {mpath}: {exc}")
        for key in ("n", "cols", "shard_rows", "dtype", "shards",
                    "fingerprint"):
            if key not in manifest:
                raise ShardError(f"{mpath}: manifest missing {key!r}")
        self.manifest = manifest
        missing = [s["name"] for s in manifest["shards"]
                   if not os.path.exists(os.path.join(directory, s["name"]))]
        if missing and rebuilder is None:
            raise ShardError(
                f"store at {directory} is missing {len(missing)} shard(s) "
                f"to rebuild: {', '.join(missing)}")

    # -- identity ------------------------------------------------------------
    @property
    def n(self) -> int:
        return int(self.manifest["n"])

    @property
    def cols(self) -> int:
        return int(self.manifest["cols"])

    @property
    def dtype(self) -> str:
        return str(self.manifest["dtype"])

    @property
    def group(self) -> int:
        return int(self.manifest.get("group", GROUP_ROWS))

    @property
    def shard_rows(self) -> int:
        return int(self.manifest["shard_rows"])

    @property
    def n_shards(self) -> int:
        return len(self.manifest["shards"])

    @property
    def fingerprint(self) -> str:
        return str(self.manifest["fingerprint"])

    def shard_range(self, i: int) -> Tuple[int, int]:
        lo = i * self.shard_rows
        return lo, min(lo + self.shard_rows, self.n)

    # -- verified read path --------------------------------------------------
    def _read_bytes(self, i: int, path: str) -> bytes:
        attempt = 0
        while True:
            try:
                _fault_check("shard_read", shard=i)
                _fault_check("shard_corrupt", shard=i, path=path)
                t0 = self.trace.begin("disk", "shard_read")
                with open(path, "rb") as f:
                    buf = f.read()
                self.stats.read_seconds += self.trace.end(
                    t0, shard=i, bytes=len(buf))
                self.stats.shards_read += 1
                self.stats.bytes_read += len(buf)
                if attempt:
                    self.trace.instant("recovery", "shard_read_ok", shard=i,
                                       attempts=attempt + 1)
                return buf
            except FileNotFoundError:
                raise                       # not transient: route to rebuild
            except Exception as exc:
                retryable = (isinstance(exc, OSError)
                             or classify_error(exc) == "transient")
                if not retryable or attempt >= self.retries:
                    raise
                self.stats.retries += 1
                self.trace.instant("fault", "shard_read_retry", shard=i,
                                   error=type(exc).__name__)
                time.sleep(self.retry_backoff * (2 ** attempt))
                attempt += 1

    def _read_verified(self, i: int, entry: Dict[str, object],
                       path: str) -> Dict[str, object]:
        buf = self._read_bytes(i, path)
        if self.verify:
            self.stats.verifications += 1
        try:
            parsed = _parse_shard(buf, path, verify=self.verify)
        except ShardCorruptionError:
            if self.verify:
                self.stats.checksum_failures += 1
            raise
        lo, hi = self.shard_range(i)
        ok = (parsed["rows"] == hi - lo and parsed["cols"] == self.cols
              and parsed["dtype"] == self.dtype)
        if self.verify:
            ok = ok and f"{_digest((buf[:len(buf) - FOOTER_BYTES],)):016x}" \
                == entry["digest"]
        if not ok:
            # internally consistent but NOT the shard the manifest promised
            # (stale or foreign file swapped in) — same recovery as bit rot
            self.stats.checksum_failures += 1
            raise ShardCorruptionError(
                f"{path}: contents do not match the manifest entry")
        return parsed

    def _quarantine(self, i: int, path: str) -> None:
        qdir = os.path.join(self.directory, QUARANTINE_DIR)
        os.makedirs(qdir, exist_ok=True)
        try:
            os.replace(path, os.path.join(qdir, os.path.basename(path)))
        except FileNotFoundError:
            pass
        self.stats.quarantined += 1

    def _rebuild(self, i: int, entry: Dict[str, object], path: str) -> None:
        lo, hi = self.shard_range(i)
        out = self.rebuilder(lo, hi)
        rows, labels = out if isinstance(out, tuple) else (out, None)
        rows = np.asarray(rows, np.float32)
        if rows.shape != (hi - lo, self.cols):
            raise ShardError(f"rebuilder returned {rows.shape} for shard {i}"
                             f" (rows [{lo}, {hi}) of {self.cols} cols)")
        if self.dtype == "int8":
            values, scales = quantize_rows(rows, self.group, symmetric=True)
        else:
            values, scales = rows, None
        if self.manifest.get("labels") and labels is None:
            raise ShardError(f"rebuilder returned no labels for shard {i} "
                             f"of a labelled store")
        buffers, digest = _pack_shard(
            values, scales,
            np.asarray(labels, np.float64) if labels is not None else None,
            self.dtype, self.group)
        if f"{digest:016x}" != entry["digest"]:
            raise ShardError(
                f"rebuild of shard {i} does not reproduce the manifest "
                f"digest — the source changed since ingest; re-ingest "
                f"instead of resuming")
        nbytes = _fsync_write(path, buffers)
        self.stats.shards_written += 1
        self.stats.bytes_written += nbytes
        self.stats.rebuilt += 1
        self.trace.instant("recovery", "shard_rebuilt", shard=i)

    def _load(self, i: int) -> Dict[str, object]:
        """Parsed payload of shard i after verify / retry / rebuild."""
        entry = self.manifest["shards"][i]
        path = os.path.join(self.directory, str(entry["name"]))
        last: Optional[BaseException] = None
        for attempt in range(2):   # original read + one post-rebuild read
            try:
                return self._read_verified(i, entry, path)
            except FileNotFoundError as exc:
                last, reason = exc, "missing"
            except ShardCorruptionError as exc:
                last, reason = exc, "corrupt"
                self.trace.instant("fault", "shard_corrupt", shard=i,
                                   path=path)
                self._quarantine(i, path)
            if attempt or self.rebuilder is None:
                break
            self._rebuild(i, entry, path)
        raise ShardCorruptionError(
            f"shard {entry['name']} of {self.directory} is {reason}"
            + ("" if self.rebuilder is not None
               else " and no rebuilder is attached; rebuild it from source"
                    " or re-ingest")) from last

    # -- decoded access ------------------------------------------------------
    def _decoded(self, i: int) -> np.ndarray:
        """f32 rows of shard i, through a small LRU of decoded shards."""
        with self._lock:
            hit = self._cache.get(i)
            if hit is not None:
                self._cache.move_to_end(i)
                return hit
            parsed = self._load(i)
            if parsed["dtype"] == "int8":
                rows = dequantize_rows(parsed["values"], parsed["scales"],
                                       parsed["group"])
            else:
                rows = np.array(parsed["values"], np.float32)  # own the bytes
            if self._cache_shards:
                self._cache[i] = rows
                while len(self._cache) > self._cache_shards:
                    self._cache.popitem(last=False)
            return rows

    def _decoded_slice(self, i: int, a: int, b: int) -> np.ndarray:
        """f32 rows [a, b) local to shard i.  With the decoded cache off
        (``cache_shards=0``, the pure re-stream mode) only the requested
        range is dequantised (`quant.dequantize_rows_range`)."""
        with self._lock:
            hit = self._cache.get(i)
            if hit is not None:
                self._cache.move_to_end(i)
                return hit[a:b]
            if self._cache_shards:
                return self._decoded(i)[a:b]
            parsed = self._load(i)
            if parsed["dtype"] == "int8":
                return dequantize_rows_range(parsed["values"],
                                             parsed["scales"], a, b,
                                             parsed["group"])
            return np.array(parsed["values"][a:b], np.float32)

    def read_shard(self, i: int, *, wire: bool = False
                   ) -> Union[np.ndarray, QuantBlock]:
        """One shard's rows: decoded f32, or the stored `QuantBlock` codes
        (``wire=True``, int8 stores) for zero-recode streaming."""
        if wire:
            if self.dtype != "int8":
                raise ShardError("wire=True requires an int8 store")
            with self._lock:
                parsed = self._load(i)
            return QuantBlock(values=np.ascontiguousarray(parsed["values"]),
                              scales=np.ascontiguousarray(parsed["scales"],
                                                          np.float32),
                              group=parsed["group"])
        return self._decoded(i)

    def iter_blocks(self, *, wire: bool = False
                    ) -> Iterator[Union[np.ndarray, QuantBlock]]:
        """Per-shard blocks in row order — the epoch re-stream entry point
        for `stream_factor_blocks`."""
        for i in range(self.n_shards):
            yield self.read_shard(i, wire=wire)

    def read_rows(self, lo: int, hi: int) -> np.ndarray:
        """Contiguous f32 rows [lo, hi) across shard boundaries."""
        lo = max(0, lo)
        hi = min(self.n, hi)
        if hi <= lo:
            return np.empty((0, self.cols), np.float32)
        first, last = lo // self.shard_rows, (hi - 1) // self.shard_rows
        if first == last:
            base = first * self.shard_rows
            return self._decoded_slice(first, lo - base, hi - base)
        out = np.empty((hi - lo, self.cols), np.float32)
        for i in range(first, last + 1):
            s, e = self.shard_range(i)
            a, b = max(s, lo), min(e, hi)
            out[a - lo:b - lo] = self._decoded_slice(i, a - s, b - s)
        return out

    def gather_rows(self, rows) -> np.ndarray:
        """f32 gather of arbitrary global rows (landmark selection, the
        stage-2 active-set recompaction, fold validation sets)."""
        rows = np.asarray(rows)
        if rows.ndim == 0:
            rows = rows[None]
        rows = np.where(rows < 0, rows + self.n, rows).astype(np.int64)
        if rows.size and (rows.min() < 0 or rows.max() >= self.n):
            raise IndexError(f"row index out of range for n={self.n}")
        out = np.empty((len(rows), self.cols), np.float32)
        for i in np.unique(rows // self.shard_rows):
            lo, _ = self.shard_range(int(i))
            mask = (rows // self.shard_rows) == i
            out[mask] = self._decoded(int(i))[rows[mask] - lo]
        return out

    def labels(self) -> np.ndarray:
        """Concatenated per-shard label vectors (dataset stores)."""
        if not self.manifest.get("labels"):
            raise ShardError(f"store at {self.directory} carries no labels")
        with self._lock:
            if self._labels is None:
                parts = []
                for i in range(self.n_shards):
                    parsed = self._load(i)
                    if parsed["labels"] is None:
                        raise ShardCorruptionError(
                            f"shard {i} is missing its label section")
                    parts.append(parsed["labels"])
                self._labels = np.concatenate(parts)
            return self._labels

    def verify_all(self) -> List[int]:
        """Force-read every shard; returns the indices that needed rebuild
        (or raises naming the first unrecoverable one)."""
        before = self.stats.rebuilt
        for i in range(self.n_shards):
            with self._lock:
                self._load(i)
        return list(range(before, self.stats.rebuilt))


class GShardView:
    """Read-only 2-D array facade over an f32 G shard store.

    Quacks enough like the host-resident ``np.ndarray`` G that the streamed
    stage-2 stack — `iter_shared_blocks` tile slices, `_recompact` fancy
    gathers, `group_scales` wire tables, `predict_from_factor` matmuls —
    runs unchanged while every row served crosses a verified checksum.
    `resilience.g_fingerprint` picks up `g_fingerprint` (derived from the
    store manifest) so a `--resume` against a mutated store is refused.
    """

    is_shard_view = True

    def __init__(self, store: ShardStore):
        if store.dtype != "f32":
            raise ShardError("G spill shards must be f32 — stage-2 wire "
                             "parity across dtypes re-encodes from f32")
        self.store = store
        self.shape = (store.n, store.cols)
        self.dtype = np.dtype(np.float32)
        self.ndim = 2

    @property
    def nbytes(self) -> int:
        return self.shape[0] * self.shape[1] * 4

    @property
    def g_fingerprint(self) -> float:
        # top 52 bits of the manifest fingerprint: exact as a float64, and
        # any store mutation (different shard digests) changes it
        return float(int(self.store.fingerprint[:13], 16))

    @property
    def rebuilder(self):
        return self.store.rebuilder

    @rebuilder.setter
    def rebuilder(self, fn) -> None:
        self.store.rebuilder = fn

    def __len__(self) -> int:
        return self.shape[0]

    def __getitem__(self, key) -> np.ndarray:
        if isinstance(key, slice):
            lo, hi, step = key.indices(self.shape[0])
            if step != 1:
                return self.store.gather_rows(np.arange(lo, hi, step))
            return self.store.read_rows(lo, hi)
        if isinstance(key, (int, np.integer)):
            return self.store.gather_rows([int(key)])[0]
        return self.store.gather_rows(key)

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        # escape hatch for incidental consumers (prediction matmuls, the
        # monolithic route); the streamed paths never materialise the view
        out = self.store.read_rows(0, self.shape[0])
        return out if dtype is None else out.astype(dtype)

    def __matmul__(self, other):
        return np.asarray(self) @ other

    def group_scales(self, group: int = GROUP_ROWS, *,
                     symmetric: bool = False) -> np.ndarray:
        """Global-row-aligned (scale, zero) table, computed shard-wise —
        identical to `quant.group_scales` over the materialised G because
        shard boundaries are multiples of GROUP_ROWS (writer invariant)."""
        if group < 1 or self.store.shard_rows % group:
            return quant_group_scales(np.asarray(self), group,
                                      symmetric=symmetric)
        parts = [quant_group_scales(self.store.read_shard(i), group,
                                    symmetric=symmetric)
                 for i in range(self.store.n_shards)]
        return np.concatenate(parts) if parts else \
            np.zeros((0, 2), np.float32)


class ShardSpillSink:
    """Stage-1 ``out=`` target that spills streamed G row-chunks to shards.

    `stream_factor_blocks` drains chunks FIFO, so writes arrive as
    contiguous in-order slices; the sink re-blocks them into shard-sized
    pieces and `finish` returns the `GShardView` stage 2 reads back.
    """

    def __init__(self, directory: str, n: int, rank: int, *,
                 shard_rows: int = 4096,
                 stats: Optional[ShardStoreStats] = None, trace=None):
        self.shape = (n, rank)
        self.trace = trace
        self.stats = stats if stats is not None else ShardStoreStats()
        self._writer = ShardWriter(directory, rank, shard_rows=shard_rows,
                                   dtype="f32", kind="g", stats=self.stats,
                                   trace=trace)
        self.directory = directory
        self._next = 0

    def __setitem__(self, key, value) -> None:
        if not isinstance(key, slice) or key.step not in (None, 1):
            raise TypeError("spill sink only accepts contiguous row slices")
        lo, hi, _ = key.indices(self.shape[0])
        if lo != self._next:
            raise ShardError(f"spill writes must be in-order: got rows "
                             f"[{lo}, {hi}) after {self._next}")
        self._writer.append(np.asarray(value, np.float32))
        self._next = hi

    def finish(self, *, rebuilder: Optional[Callable] = None,
               verify: bool = True, retries: int = 0,
               retry_backoff: float = 0.05) -> GShardView:
        if self._next != self.shape[0]:
            raise ShardError(f"spill received {self._next} of "
                             f"{self.shape[0]} rows")
        self._writer.finish()
        store = ShardStore(self.directory, verify=verify, retries=retries,
                           retry_backoff=retry_backoff, rebuilder=rebuilder,
                           stats=self.stats, trace=self.trace)
        return GShardView(store)


# -- LIBSVM ingest (one parse, ever) ----------------------------------------

def ingest_libsvm_shards(path: str, directory: str, *,
                         n_features: Optional[int] = None,
                         shard_rows: int = 4096, dtype: str = "f32",
                         group: int = GROUP_ROWS, on_bad_row: str = "raise",
                         stats: Optional[ShardStoreStats] = None,
                         trace=None) -> ShardStore:
    """Parse a LIBSVM text file ONCE into a labelled shard store.

    With ``n_features`` given the parse is fully streaming
    (`read_libsvm_blocks` — the dense matrix never materialises); without
    it, one `read_libsvm` pass infers the width (still a single parse).
    The manifest records the row counts and the source fingerprint, so
    `open_or_ingest` re-runs skip the text entirely — closing the old
    double-parse (`count_libsvm_rows` + block reader) of text re-runs.
    """
    from repro.data.libsvm_format import (IngestStats, read_libsvm,
                                          read_libsvm_blocks)
    ing = IngestStats()
    src = source_fingerprint(path)
    extra = {"on_bad_row": on_bad_row, "source_path": os.path.abspath(path)}

    def _writer(cols):
        return ShardWriter(directory, cols, shard_rows=shard_rows,
                           dtype=dtype, group=group, kind="dataset",
                           with_labels=True, source=src, extra=extra,
                           stats=stats, trace=trace)

    if n_features:
        w = _writer(n_features)
        for dense, labels in read_libsvm_blocks(
                path, rows=shard_rows, n_features=n_features,
                on_bad_row=on_bad_row, stats=ing):
            w.append(dense, labels)
    else:
        data = read_libsvm(path, on_bad_row=on_bad_row, stats=ing)
        w = _writer(data.n_features)
        for dense, labels in data.iter_dense_blocks(shard_rows):
            w.append(dense, labels)
    w.extra = extra   # ensure counts below land in the manifest
    extra["rows_read"] = ing.rows_read
    extra["rows_skipped"] = ing.rows_skipped
    w.finish()
    store = ShardStore(directory, stats=stats, trace=trace)
    attach_source_rebuilder(store, path, on_bad_row=on_bad_row)
    return store


def attach_source_rebuilder(store: ShardStore, path: str, *,
                            on_bad_row: str = "raise") -> ShardStore:
    """Arm a dataset store to regenerate any shard by re-parsing its row
    range from the original LIBSVM text (bit-equal codes by construction:
    the codec is deterministic and scale groups are shard-aligned)."""
    from repro.data.libsvm_format import read_libsvm_rows_range

    cols = store.cols

    def rebuild(lo: int, hi: int):
        return read_libsvm_rows_range(path, lo, hi, cols,
                                      on_bad_row=on_bad_row)

    store.rebuilder = rebuild
    return store


def open_or_ingest(path: str, directory: str, *,
                   n_features: Optional[int] = None, shard_rows: int = 4096,
                   dtype: str = "f32", group: int = GROUP_ROWS,
                   on_bad_row: str = "raise", verify: bool = True,
                   retries: int = 0, retry_backoff: float = 0.05,
                   stats: Optional[ShardStoreStats] = None,
                   trace=None) -> Tuple[ShardStore, bool]:
    """Reuse a matching shard store, or ingest the text once to build it.

    Returns ``(store, ingested)``.  Reuse requires the manifest's recorded
    source fingerprint AND ingest parameters to match — anything else
    (edited text, different shard_rows/dtype/width) re-ingests, so a reused
    store is never silently wrong.  A reused run performs ZERO text parses:
    n, width, labels, and row counts all come from the manifest/shards.
    """
    try:
        store = ShardStore(directory, verify=verify, retries=retries,
                           retry_backoff=retry_backoff, stats=stats,
                           trace=trace)
        m = store.manifest
        if (m.get("kind") == "dataset" and m.get("labels")
                and m.get("source") == source_fingerprint(path)
                and store.shard_rows == shard_rows
                and store.dtype == dtype
                and (not n_features or store.cols == n_features)):
            attach_source_rebuilder(store, path, on_bad_row=on_bad_row)
            return store, False
    except ShardError:
        pass
    store = ingest_libsvm_shards(
        path, directory, n_features=n_features, shard_rows=shard_rows,
        dtype=dtype, group=group, on_bad_row=on_bad_row, stats=stats,
        trace=trace)
    store.verify = verify
    store.retries = int(retries)
    store.retry_backoff = float(retry_backoff)
    return store, True
