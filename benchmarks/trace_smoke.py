"""Observability smoke: the tracer must see the whole pipeline and cost
nothing when disabled.

Three assertions, CI-fatal on regression:

  1. **Coverage** — one streamed multi-class fit under a `Tracer` exports
     Chrome-trace JSON that loads back with >= 1 span in every core
     category (read / h2d / dispatch / d2h / epoch): an instrumentation
     hole in a hot path fails here, not in a production trace.
  2. **No-op** — a live but uninstalled spy tracer records ZERO events
     across the same fit: the default path really is the `NULL` fast path.
  3. **Overhead** — the disabled `NULL.begin()`/`end()` pair stays within a
     small multiple of a bare `perf_counter` pair (it IS two perf_counter
     calls plus a subtract), so leaving instrumentation in hot loops is
     free in the shipped configuration.

Writes the validated trace to ``TRACE_SMOKE_JSON`` (default
``/tmp/trace_smoke.json``) so CI can upload it as an artifact.

    PYTHONPATH=src python -m benchmarks.run trace_smoke
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.common import emit

OUT_PATH = os.environ.get("TRACE_SMOKE_JSON", "/tmp/trace_smoke.json")
REQUIRED_CATEGORIES = ("read", "h2d", "dispatch", "d2h", "epoch")

# disabled begin/end vs bare perf_counter pair; generous bound — this guards
# against accidentally routing the NULL path through recording, not against
# scheduler noise
OVERHEAD_MULT = 25.0


def _traced_fit(trace):
    from repro.core import KernelParams, StreamConfig
    from repro.core.svm import LPDSVM
    from repro.data import make_multiclass

    x, y = make_multiclass(400, p=6, n_classes=3, seed=11)
    svm = LPDSVM(KernelParams("rbf", gamma=0.25), C=2.0, budget=64,
                 stream=True,
                 stream_config=StreamConfig(chunk_rows=128, tile_rows=128))
    svm.fit(x, y, trace=trace)
    return svm


def run() -> None:
    from repro.core.trace import NULL, Tracer

    # 1. coverage: every core category shows up in the exported JSON
    tr = Tracer()
    t0 = time.perf_counter()
    _traced_fit(tr)
    fit_s = time.perf_counter() - t0
    tr.export(OUT_PATH)
    d = json.load(open(OUT_PATH))
    spans = [e for e in d["traceEvents"] if e["ph"] == "X"]
    by_cat = {}
    for e in spans:
        by_cat[e["cat"]] = by_cat.get(e["cat"], 0) + 1
    missing = [c for c in REQUIRED_CATEGORIES if not by_cat.get(c)]
    assert not missing, f"trace missing categories {missing}: {by_cat}"
    summary = tr.summary()
    assert "effective H2D" in summary and "rows/s" in summary
    emit("trace_smoke_coverage", fit_s * 1e6,
         f"{len(spans)} spans over {len(by_cat)} categories -> {OUT_PATH}")

    # 2. no-op: an uninstalled tracer must never hear from the pipeline
    spy = Tracer()
    _traced_fit(None)
    assert spy.n_events == 0, \
        f"disabled-mode leak: spy recorded {spy.n_events} events"
    emit("trace_smoke_noop", 0.0, "uninstalled spy saw 0 events")

    # 3. overhead: NULL.begin/end vs a bare perf_counter pair
    reps = 20000

    def loop_null():
        t = 0.0
        for _ in range(reps):
            t0 = NULL.begin("h2d", "put")
            t += NULL.end(t0)
        return t

    def loop_bare():
        t = 0.0
        for _ in range(reps):
            t0 = time.perf_counter()
            t += time.perf_counter() - t0
        return t

    loop_null(), loop_bare()            # warm
    t0 = time.perf_counter(); loop_bare(); bare = time.perf_counter() - t0
    t0 = time.perf_counter(); loop_null(); null = time.perf_counter() - t0
    ratio = null / max(bare, 1e-12)
    assert ratio < OVERHEAD_MULT, \
        f"NULL begin/end {ratio:.1f}x a perf_counter pair (cap {OVERHEAD_MULT})"
    emit("trace_smoke_null_overhead", null / reps * 1e6,
         f"{ratio:.2f}x bare perf_counter pair")


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
