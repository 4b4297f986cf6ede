"""Reduce a `jax.profiler` trace (`.xplane.pb`) to the benchmark's device
numbers.

  busy_s       union of the device's program executions (the "XLA Modules"
               line; the "XLA Ops" line where a plane has no module line)
               inside the traced window, averaged over the device planes
  window_s     the length of the host span named ``window``
  modules      device seconds per program, by name without its "(id)"
  ops          device seconds per operation name ("XLA Ops" line)
  gaps         idle intervals of the device inside the window, each named
               by the innermost host span that holds its midpoint
  idle_by_span idle seconds per host span name

Host spans are the `jax.profiler.TraceAnnotation`s the benchmark writes
around its calls.  Nothing here touches a device; the reduction runs on
plain event lists, so it is tested on hand-built ones.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "window"
HOST_SPANS = ("stage1", "stage2", "predict", "between_jobs")
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class DevicePlane:
    name: str
    modules: List[Event]
    ops: List[Event]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def complement(busy, lo: float, hi: float):
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def _strip_id(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name).strip()


def innermost(spans: Sequence[Event], t: float) -> str:
    """Name of the shortest host span holding time ``t``."""
    best: Optional[Event] = None
    for sp in spans:
        if sp.start_ns <= t <= sp.end_ns and (best is None
                                              or sp.dur_ns < best.dur_ns):
            best = sp
    return best.name if best is not None else "outside_spans"


def reduce_events(planes: Sequence[DevicePlane], host_spans: Sequence[Event],
                  top: int = 10) -> dict:
    """The numbers listed in the module docstring, from device planes and
    the benchmark's host spans (one of them named ``window``)."""
    windows = [s for s in host_spans if s.name == WINDOW_SPAN]
    if not windows or not planes:
        raise ValueError("trace holds no window span or no device plane")
    lo = min(s.start_ns for s in windows)
    hi = max(s.end_ns for s in windows)
    spans = [s for s in host_spans if s.name in HOST_SPANS]
    busy_total, gaps, idle = 0.0, [], collections.Counter()
    modules, ops = collections.Counter(), collections.Counter()
    for pl in planes:
        execs = pl.modules or pl.ops
        busy = clip(union((e.start_ns, e.end_ns) for e in execs), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for s, e in complement(busy, lo, hi):
            name = innermost(spans, 0.5 * (s + e))
            gaps.append((name, (e - s) * 1e-9))
            idle[name] += (e - s) * 1e-9
        for e in pl.modules:
            if e.end_ns > lo and e.start_ns < hi:
                modules[_strip_id(e.name)] += e.dur_ns * 1e-9
        for e in pl.ops:
            if e.end_ns > lo and e.start_ns < hi:
                ops[e.name] += e.dur_ns * 1e-9
    gaps.sort(key=lambda g: -g[1])
    return {
        "busy_s": busy_total * 1e-9 / len(planes),
        "window_s": (hi - lo) * 1e-9,
        "modules": dict(modules),
        "ops": dict(ops),
        "gaps": gaps[:top],
        "idle_by_span": dict(idle),
        "breakdown": {
            "device_ops": [[k, v] for k, v in ops.most_common(top)]
            or [[k, v] for k, v in modules.most_common(top)],
            "idle_gaps": [[k, v] for k, v in gaps[:top]],
        },
    }


def load(path: str, device_prefix: str = "/device:TPU"):
    """(device planes, host spans) of one `.xplane.pb`."""
    from jax.profiler import ProfileData
    planes, spans = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(device_prefix):
            lines = {ln.name: ln for ln in plane.lines}
            ev = lambda ln: [Event(e.name, e.start_ns, e.duration_ns)
                             for e in ln.events] if ln is not None else []
            planes.append(DevicePlane(plane.name, ev(lines.get(MODULE_LINE)),
                                      ev(lines.get(OPS_LINE))))
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                             for e in ln.events
                             if e.name == WINDOW_SPAN or e.name in HOST_SPANS)
    return planes, spans


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def reduce_trace(log_dir: str, device_prefix: str = "/device:TPU") -> dict:
    planes, spans = load(find_xplane(log_dir), device_prefix)
    return reduce_events(planes, spans)
