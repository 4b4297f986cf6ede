"""Process-wide span/counter tracer for the streaming pipelines.

The paper's thesis is "fully exploit the machine"; this module is how we
*check* that claim on ourselves.  The stats dataclasses
(`Stage1StreamStats`, `Stage2StreamStats`, ...) stay the assertable source
of truth for byte/second totals — the tracer is the timeline view over the
same measurements: every hot-path `perf_counter` pair becomes a *span*
``(category, name, t_start, t_end, thread, attrs)`` whose duration still
feeds the stats field it always fed, plus instant events (cache hits,
evictions) and gauge samples (queue depth).

Span categories name what the host is doing, so that a gap on the device
can be put down to it: ``h2d`` (a host-to-device put), ``d2h`` (a blocking
device-to-host read), ``dispatch`` (the enqueue of a device program — the
program itself runs asynchronously), ``read`` (host staging of a G block),
and bookkeeping categories (``engine``, ``compact``, ``epoch``, ``fit``,
``stage1``, ``stage2``, ``predict``, ...).

Design constraints, in order:

1. **Near-zero overhead when disabled.**  The module-level `NULL` tracer is
   what every call site sees by default; its `begin()`/`end()` still return
   `perf_counter` readings (so `put_seconds` etc. keep their exact
   pre-tracer meanings) but record nothing, allocate nothing, and take no
   lock.  Solver outputs with tracing disabled are bit-identical to the
   un-instrumented code.
2. **Thread safety.**  The stage-2 farm runs one worker thread per device
   behind a shared reader; recording is a single append of an immutable
   tuple under one lock, and export snapshots under the same lock.
3. **Two sinks.**  Every span of a `Tracer` opens a
   `jax.profiler.TraceAnnotation` named ``<category>/<name>``, so spans
   land in a `jax.profiler` trace on the same clock as the device's
   programs (an annotation costs well under a microsecond when no
   profiler is running).  ``keep=True`` (the default) also records in
   memory for ``export(path)`` (Chrome-trace/Perfetto JSON, one row per
   thread) and ``summary()`` (seconds per category, effective H2D GB/s,
   rows/s); ``Tracer(keep=False)`` mirrors and keeps nothing.  Attrs stay
   in the in-memory record only; instants and counters are not mirrored.

Usage::

    tr = Tracer()
    with tr.span("h2d", "put_block", bytes=nbytes): ...
    # or the stats-feeding pair form (the name is given at `begin`, because
    # a profiler annotation cannot be backdated):
    t0 = tr.begin("h2d", "put_block")
    ...
    stats.put_seconds += tr.end(t0, bytes=nbytes)
    tr.export("trace.json"); print(tr.summary())

    # one combined host + device trace:
    with jax.profiler.trace(log_dir):
        install(Tracer(keep=False))
        try:
            svm.fit(x, y)
        finally:
            uninstall()

Call sites resolve their tracer via `resolve(explicit)`: an explicitly
passed tracer wins, else the process-wide one set by `install()`, else
`NULL`.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Callable, Dict, List, Optional

__all__ = [
    "Tracer", "NullTracer", "NULL", "ProgressPrinter",
    "install", "uninstall", "active", "resolve",
]

# Event record layout (immutable tuple — one allocation per record):
#   (ph, category, name, t_abs, dur, tid, attrs)
# ph: "X" complete span | "i" instant | "C" counter sample
# t_abs/dur in perf_counter seconds; attrs a (possibly empty) dict.
_SPAN, _INSTANT, _COUNTER = "X", "i", "C"


class _NullSpan:
    """Shared no-op context manager returned by `NullTracer.span`."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Disabled-mode tracer: the module-level no-op fast path.

    `begin`/`end` still bracket the region with `perf_counter` so durations
    returned to stats fields keep their exact meanings; nothing is recorded,
    no lock is taken, no allocation happens."""

    __slots__ = ()
    enabled = False

    def begin(self, category: str, name: str) -> float:
        return time.perf_counter()

    def end(self, t0: float, **attrs) -> float:
        return time.perf_counter() - t0

    def span(self, category: str, name: str, **attrs):
        return _NULL_SPAN

    def instant(self, category: str, name: str, **attrs) -> None:
        pass

    def counter(self, name: str, value) -> None:
        pass

    def add_listener(self, fn: Callable) -> None:
        pass


NULL = NullTracer()


class _Span:
    """Context-manager span for sites that do not feed a stats field."""

    __slots__ = ("_tracer", "category", "name", "attrs", "_t0", "_mark")

    def __init__(self, tracer: "Tracer", category: str, name: str,
                 attrs: dict):
        self._tracer = tracer
        self.category = category
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        self._mark = self._tracer._open(self.category, self.name)
        self._t0 = time.perf_counter()
        return self

    def set(self, **attrs):
        """Attach attrs discovered mid-span (e.g. result sizes)."""
        self.attrs.update(attrs)
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._mark.__exit__(None, None, None)
        self._tracer._record(_SPAN, self.category, self.name, self._t0,
                             t1 - self._t0, self.attrs)
        return False


class Tracer:
    """Thread-safe span/instant/counter recorder: spans into the
    `jax.profiler` trace, and everything in memory unless ``keep`` is
    off."""

    enabled = True

    def __init__(self, keep: bool = True):
        import jax.profiler
        self.keep = keep
        self._annotate = jax.profiler.TraceAnnotation
        self._lock = threading.Lock()
        self._events: List[tuple] = []
        self._thread_names: Dict[int, str] = {}
        self._listeners: List[Callable] = []
        self.pid = os.getpid()
        self.t0 = time.perf_counter()

    # ---- recording ------------------------------------------------------
    def begin(self, category: str, name: str) -> tuple:
        """Start a stats-feeding span and open its profiler annotation;
        pair with `end`, which takes the handle this returns."""
        mark = self._open(category, name)
        return (category, name, time.perf_counter(), mark)

    def end(self, handle: tuple, **attrs) -> float:
        """Close a `begin` span, record it, and return its duration so call
        sites can feed the existing stats field in the same expression."""
        t1 = time.perf_counter()
        category, name, t0, mark = handle
        mark.__exit__(None, None, None)
        self._record(_SPAN, category, name, t0, t1 - t0, attrs)
        return t1 - t0

    def span(self, category: str, name: str, **attrs) -> _Span:
        """Context-manager span for non-stats regions."""
        return _Span(self, category, name, attrs)

    def instant(self, category: str, name: str, **attrs) -> None:
        """Point event (cache hit/miss/evict, ...)."""
        self._record(_INSTANT, category, name, time.perf_counter(), 0.0,
                     attrs)

    def counter(self, name: str, value) -> None:
        """Gauge sample (queue depth, active rows, ...)."""
        self._record(_COUNTER, "counter", name, time.perf_counter(), 0.0,
                     {"value": float(value)})

    def add_listener(self, fn: Callable) -> None:
        """Subscribe ``fn(event_tuple)`` to every record (e.g. the per-epoch
        progress printer).  Listeners run on the recording thread, outside
        the lock — keep them cheap and thread-safe."""
        self._listeners.append(fn)

    def _open(self, category: str, name: str):
        """The profiler annotation of a span, entered."""
        mark = self._annotate(f"{category}/{name}")
        mark.__enter__()
        return mark

    def _record(self, ph: str, category: str, name: str, t_abs: float,
                dur: float, attrs: dict) -> None:
        tid = threading.get_ident()
        ev = (ph, category, name, t_abs, dur, tid, attrs)
        if self.keep:
            with self._lock:
                if tid not in self._thread_names:
                    self._thread_names[tid] = threading.current_thread().name
                self._events.append(ev)
        for fn in self._listeners:
            fn(ev)

    # ---- introspection --------------------------------------------------
    @property
    def n_events(self) -> int:
        with self._lock:
            return len(self._events)

    def events(self) -> List[tuple]:
        """Snapshot of all records (immutable tuples, safe to share)."""
        with self._lock:
            return list(self._events)

    def categories(self) -> Dict[str, int]:
        """Record count per category."""
        out: Dict[str, int] = {}
        for ev in self.events():
            out[ev[1]] = out.get(ev[1], 0) + 1
        return out

    # ---- export ---------------------------------------------------------
    def export(self, path: str) -> None:
        """Write Chrome-trace/Perfetto JSON (load in ui.perfetto.dev or
        chrome://tracing).  Timestamps are µs relative to tracer creation;
        one timeline row per recording thread, named after the thread."""
        with self._lock:
            events = list(self._events)
            names = dict(self._thread_names)
        out = []
        for tid, tname in sorted(names.items()):
            out.append({"ph": "M", "name": "thread_name", "pid": self.pid,
                        "tid": tid, "args": {"name": tname}})
        for ph, cat, name, t_abs, dur, tid, attrs in events:
            ts = (t_abs - self.t0) * 1e6
            ev = {"ph": ph, "cat": cat, "name": name, "ts": ts,
                  "pid": self.pid, "tid": tid}
            if ph == _SPAN:
                ev["dur"] = dur * 1e6
                if attrs:
                    ev["args"] = attrs
            elif ph == _INSTANT:
                ev["s"] = "t"
                if attrs:
                    ev["args"] = attrs
            else:  # counter
                ev["args"] = attrs
            out.append(ev)
        payload = {"traceEvents": out, "displayTimeUnit": "ms",
                   "otherData": {"tool": "repro.core.trace"}}
        with open(path, "w") as f:
            json.dump(payload, f, default=_json_default)

    # ---- aggregation ----------------------------------------------------
    def summary(self) -> str:
        """Aggregated text view: seconds/records per category, effective
        H2D GB/s and rows/s."""
        events = self.events()
        spans = [e for e in events if e[0] == _SPAN]
        if not events:
            return "trace: no events recorded"
        by_cat: Dict[str, List[tuple]] = {}
        for e in spans:
            by_cat.setdefault(e[1], []).append(e)
        t_lo = min(e[3] for e in events)
        t_hi = max(e[3] + e[4] for e in events)
        wall = max(t_hi - t_lo, 1e-12)

        lines = [f"trace summary ({len(events)} events, "
                 f"{len(self._thread_names)} threads, wall {wall:.3f}s)"]
        for cat in sorted(by_cat):
            evs = by_cat[cat]
            secs = sum(e[4] for e in evs)
            nbytes = sum(e[6].get("bytes", 0) for e in evs)
            line = f"  {cat:<8s} {len(evs):6d} spans  {secs:9.3f}s"
            if nbytes:
                line += (f"  {nbytes / 1e9:8.3f} GB"
                         f"  {nbytes / max(secs, 1e-12) / 1e9:7.2f} GB/s")
            lines.append(line)

        h2d = by_cat.get("h2d", [])
        h2d_secs = sum(e[4] for e in h2d)
        h2d_bytes = sum(e[6].get("bytes", 0) for e in h2d)
        if h2d_bytes:
            lines.append(f"  effective H2D: "
                         f"{h2d_bytes / max(h2d_secs, 1e-12) / 1e9:.2f} GB/s "
                         f"({h2d_bytes / 1e9:.3f} GB in {h2d_secs:.3f}s)")
        rows = sum(e[6].get("rows", 0) for e in by_cat.get("dispatch", []))
        if rows:
            lines.append(f"  rows/s: {rows / wall:,.0f} "
                         f"({rows:,} row visits in {wall:.3f}s wall)")
        for cat, label in (("cache", "cache events"),
                           ("fault", "fault events"),
                           ("recovery", "recovery events")):
            inst = {}
            for e in events:
                if e[0] == _INSTANT and e[1] == cat:
                    inst[e[2]] = inst.get(e[2], 0) + 1
            if inst:
                lines.append(f"  {label}: " + ", ".join(
                    f"{k}={v}" for k, v in sorted(inst.items())))
        return "\n".join(lines)


def _json_default(o):
    """numpy scalars and other non-JSON attrs degrade gracefully."""
    try:
        return float(o)
    except (TypeError, ValueError):
        return str(o)


class ProgressPrinter:
    """Event listener printing one line per stage-2 epoch (`--verbose`).

    Subscribes to the driver's per-epoch ``epoch`` spans, whose attrs carry
    the aggregated counters (active rows, bytes moved, cache hit rate, row
    visits, max KKT violation); everything on the line comes from the same
    event stream the trace file records."""

    def __init__(self, stream=None):
        import sys
        self._out = stream if stream is not None else sys.stderr

    def __call__(self, ev) -> None:
        ph, cat, name, t_abs, dur, tid, attrs = ev
        if ph != _SPAN or cat != "epoch":
            return
        a = attrs
        hit = a.get("hit_bytes", 0)
        miss = a.get("miss_bytes", 0)
        rate = hit / (hit + miss) if hit + miss else 0.0
        rows = a.get("rows", 0)
        viol = a.get("viol")
        viol_s = f"{viol:9.3e}" if viol is not None else "      n/a"
        print(f"epoch {a.get('epoch', '?'):>4} [{a.get('kind', '?'):<5s}] "
              f"active={a.get('active', 0):>8,} "
              f"bytes={a.get('bytes', 0) / 1e6:9.2f}MB "
              f"hit={rate:5.1%} "
              f"rows/s={rows / max(dur, 1e-12):12,.0f} "
              f"viol={viol_s} "
              f"({dur:.3f}s)", file=self._out, flush=True)


# ---- process-wide tracer ------------------------------------------------
_active: Optional[Tracer] = None


def install(tracer: Optional[Tracer]) -> None:
    """Set the process-wide tracer picked up by `resolve` everywhere."""
    global _active
    _active = tracer


def uninstall() -> None:
    """Clear the process-wide tracer (back to the no-op fast path)."""
    install(None)


def active() -> Optional[Tracer]:
    """The installed process-wide tracer, or None."""
    return _active


def resolve(tracer=None):
    """Tracer for a call site: explicit argument > installed global > NULL."""
    if tracer is not None:
        return tracer
    return _active if _active is not None else NULL
