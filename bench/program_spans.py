#!/usr/bin/env python3
"""Where the device idles, by the program's own spans.

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s>

Runs one cell of `BENCHMARK.json` as ``bench/run.py --trace 1`` does (the
job made from the seed, one set-up job, then a profiled window of the same
job back to back), with the program's tracer installed in the mode that
mirrors every span into the profiler's trace and keeps nothing
(`repro.core.trace.Tracer(keep=False)`).  Each program span is then a
host event named ``<category>/<name>`` on the device's clock.  The run
checks no answers and reports no benchmark metric; its last line of
standard output is one JSON object, per job:

  stage2_idle_s   the device's idle seconds inside the benchmark's
                  ``stage2`` spans
  exposed_s       that idle time split by the innermost program span over
                  each instant (exact interval intersection with each
                  span's self time): ``h2d`` (puts), ``d2h`` (blocking
                  reads), ``dispatch`` (program enqueues), ``host`` (every
                  other program span: engine bookkeeping, block reads,
                  recompaction, the epoch loop), and ``unspanned``, which
                  no program span covers
  by_category     the same split by each span category
  idle_by_program_span
                  the program spans that hold the most idle seconds
                  anywhere in the window, by the same split
  program_span_s  host seconds per program span name (nested spans
                  included), beside the idle seconds under them
  h2d_puts, d2h_syncs, smo_calls
                  `Stage2StreamStats` counters of the streamed stage 2

Runs on a TPU only, like `bench/run.py`.
"""
from __future__ import annotations

import argparse
import collections
import heapq
import json
import os
import re
import shutil
import sys
import tempfile
import time
from typing import List, Sequence, Tuple

if __package__ in (None, ""):
    sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))]

from bench import trace_reduce as tr  # noqa: E402

STAGE2_SPAN = "stage2"
UNSPANNED = "unspanned"
# A program span's name as the program's tracer writes it.
PROGRAM_SPAN = re.compile(r"^[a-z][a-z0-9_]*/[a-z][a-z0-9_]*$")
# Categories reported on their own; every other one is host work.
EXPOSED = ("h2d", "d2h", "dispatch")


def intersect(a, b):
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def innermost_segments(spans: Sequence[tr.Event]
                       ) -> List[Tuple[float, float, str]]:
    """The union of ``spans`` cut into ``(start, end, name)`` pieces, each
    named by the shortest span over it: the self time of every span, with
    nested spans taken out of their parents."""
    bounds = sorted({s.start_ns for s in spans} | {s.end_ns for s in spans})
    order = sorted(spans, key=lambda s: s.start_ns)
    heap: list = []
    out: List[Tuple[float, float, str]] = []
    i = 0
    for lo, hi in zip(bounds, bounds[1:]):
        while i < len(order) and order[i].start_ns <= lo:
            sp = order[i]
            heapq.heappush(heap, (sp.dur_ns, i, sp.end_ns, sp.name))
            i += 1
        while heap and heap[0][2] <= lo:
            heapq.heappop(heap)
        if not heap:
            continue
        name = heap[0][3]
        if out and out[-1][1] == lo and out[-1][2] == name:
            out[-1] = (out[-1][0], hi, name)
        else:
            out.append((lo, hi, name))
    return out


def split_idle(gaps, segments):
    """(seconds per segment name, uncovered seconds) of sorted disjoint
    ``gaps`` against the sorted disjoint named ``segments``."""
    by_name, uncovered, j = collections.Counter(), 0.0, 0
    for s, e in gaps:
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        covered, k = 0.0, j
        while k < len(segments) and segments[k][0] < e:
            lo, hi = max(s, segments[k][0]), min(e, segments[k][1])
            if hi > lo:
                by_name[segments[k][2]] += (hi - lo) * 1e-9
                covered += hi - lo
            k += 1
        uncovered += (e - s - covered) * 1e-9
    return by_name, uncovered


def category(name: str) -> str:
    return name.split("/", 1)[0]


def reduce_events(planes: Sequence[tr.DevicePlane],
                  host_spans: Sequence[tr.Event], top: int = 10) -> dict:
    """The program-span split of a trace: device planes, and host spans
    holding the benchmark's (``window``, ``stage2``) and the program's.
    Idle seconds are per device, averaged over the planes, as
    `bench/trace_reduce.py`'s ``busy_s``."""
    windows = [s for s in host_spans if s.name == tr.WINDOW_SPAN]
    if not windows or not planes:
        raise ValueError("trace holds no window span or no device plane")
    lo = min(s.start_ns for s in windows)
    hi = max(s.end_ns for s in windows)
    program = [s for s in host_spans if PROGRAM_SPAN.match(s.name)
               and s.end_ns > lo and s.start_ns < hi]
    segments = innermost_segments(program)
    stage2 = tr.clip(tr.union((s.start_ns, s.end_ns) for s in host_spans
                              if s.name == STAGE2_SPAN), lo, hi)
    by_category = collections.Counter(
        {category(s.name): 0.0 for s in program
         if intersect([(s.start_ns, s.end_ns)], stage2)})
    by_category[UNSPANNED] = 0.0
    by_span, span_s = collections.Counter(), collections.Counter()
    for s in program:
        span_s[s.name] += (min(s.end_ns, hi) - max(s.start_ns, lo)) * 1e-9
    for pl in planes:
        busy = tr.clip(tr.union((e.start_ns, e.end_ns)
                                for e in pl.modules or pl.ops), lo, hi)
        gaps = tr.complement(busy, lo, hi)
        by_span.update(split_idle(gaps, segments)[0])
        by_name, uncovered = split_idle(intersect(gaps, stage2), segments)
        for name, secs in by_name.items():
            by_category[category(name)] += secs
        by_category[UNSPANNED] += uncovered
    n = len(planes)
    return {
        "by_category": {k: v / n for k, v in by_category.items()},
        "program_spans": len(program),
        "program_span_s": dict(span_s),
        "idle_by_program_span": [[k, v / n]
                                 for k, v in by_span.most_common(top)],
    }


def exposed(by_category: dict) -> dict:
    """``by_category`` gathered into ``h2d``, ``d2h``, ``dispatch``,
    ``host`` (every other category) and ``unspanned``."""
    out = {k: by_category.get(k, 0.0) for k in (*EXPOSED, UNSPANNED)}
    out["host"] = sum(v for k, v in by_category.items()
                      if k not in (*EXPOSED, UNSPANNED))
    return out


def load(path: str) -> List[tr.Event]:
    """The host events of one `.xplane.pb` that the program's tracer wrote,
    each name cut at the ``#`` that starts an annotation's metadata."""
    from jax.profiler import ProfileData
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    name = e.name.split("#", 1)[0]
                    if PROGRAM_SPAN.match(name):
                        spans.append(tr.Event(name, e.start_ns,
                                              e.duration_ns))
    return spans


def traced_window(workload: str, seed: int, seconds: float, devices,
                  trace_dir: str, config_overrides: dict = None,
                  traffic_overrides: dict = None):
    """Set-up job, then a window of whole jobs profiled into ``trace_dir``
    with the mirroring tracer installed; returns one dict of counters per
    job of the window."""
    import jax

    from bench import data, job, run
    from repro.core import trace as program_trace

    _, _, cfg, traffic, _ = run.cell(workload, config_overrides,
                                     traffic_overrides)
    x, y, x_test, _, gamma = data.make_job(cfg, seed)
    lseed = data.landmark_seed(cfg)
    span = jax.profiler.TraceAnnotation

    def one_job():
        rec, svm, _, _ = job.run_job(cfg, traffic, gamma, lseed, x, y,
                                     x_test, span)
        s2 = svm.stats.stage2_stats
        return {"seconds": rec.seconds, "stage2_s": rec.stage2_s,
                "smo_calls": rec.kernel_calls,
                "h2d_puts": int(s2.h2d_puts) if s2 is not None else 0,
                "d2h_syncs": int(s2.d2h_syncs) if s2 is not None else 0}

    jobs = []
    with jax.default_device(devices[0]):
        one_job()
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=run.profile_options())
        program_trace.install(program_trace.Tracer(keep=False))
        try:
            t_window = time.perf_counter()
            with span(tr.WINDOW_SPAN):
                while True:
                    jobs.append(one_job())
                    if time.perf_counter() - t_window >= seconds:
                        break
        finally:
            program_trace.uninstall()
            jax.profiler.stop_trace()
    return jobs


def summarize(planes, host_spans, jobs) -> dict:
    """The result object (module docstring) of a traced window of
    ``jobs``: device planes, and the benchmark's and the program's host
    spans."""
    bench = tr.reduce_events(planes, host_spans)
    prog = reduce_events(planes, host_spans)
    n = len(jobs)
    per_job = lambda d: {k: v / n for k, v in d.items()}
    stage2_idle = sum(prog["by_category"].values())
    mean = lambda key: sum(j[key] for j in jobs) / n
    return {
        "jobs": n,
        "window_s": bench["window_s"],
        "busy_s": bench["busy_s"],
        "job_s": sum(j["seconds"] for j in jobs) / n,
        "stage2_s": mean("stage2_s"),
        "stage2_idle_s": stage2_idle / n,
        "exposed_s": per_job(exposed(prog["by_category"])),
        "unspanned_share": (prog["by_category"][UNSPANNED] / stage2_idle
                            if stage2_idle > 0 else 0.0),
        "by_category": per_job(prog["by_category"]),
        "idle_by_program_span": [[k, v / n] for k, v in
                                 prog["idle_by_program_span"]],
        "program_span_s": dict(sorted(per_job(prog["program_span_s"])
                                      .items(), key=lambda kv: -kv[1])),
        "program_spans": prog["program_spans"],
        "smo_calls": mean("smo_calls"),
        "h2d_puts": mean("h2d_puts"),
        "d2h_syncs": mean("d2h_syncs"),
    }


def main(argv=None) -> int:
    from bench import run
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [run.ROOT, os.path.join(run.ROOT, "src")]
    run.add_libtpu_flags()
    _, wl, _, _, _ = run.cell(args.workload)
    devices, err = run.tpu_devices(wl["chips"])
    if err:
        run.log(f"program_spans: {err}")
        return 1
    run.log(f"program_spans: {devices[0].device_kind}; compile cache "
            f"{run.enable_compile_cache()}")
    trace_dir = tempfile.mkdtemp(prefix="program_spans_")
    try:
        jobs = traced_window(args.workload, args.seed, args.seconds,
                             devices, trace_dir)
        path = tr.find_xplane(trace_dir)
        planes, bench_spans = tr.load(path)
        out = summarize(planes, bench_spans + load(path), jobs)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
