"""Observability substrate (core/trace.py).

Pins down (a) the Chrome-trace export schema (Perfetto-loadable JSON with
thread-name metadata, complete spans, instants, counters); (b) thread safety
under the real 2-device farm — spans arrive from the shared reader thread
AND every device worker thread; (c) the disabled-mode contract: a live but
UNINSTALLED tracer records zero events, and a traced solve is bit-identical
to an untraced one (tracing observes, never steers); (d) the derived-rate
properties (`h2d_gbps`, `overlap_efficiency`) shared by the stats
dataclasses and the benchmarks; (e) the profiler mirror: every span opens
one `<category>/<name>` annotation, nested as the spans are, and a real
`jax.profiler` trace of a streamed fit holds them; (f) the exact
`h2d_puts` / `d2h_syncs` counts of a streamed solve.
"""
import collections
import io
import json
import threading
import time

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (KernelParams, SolverConfig, StreamConfig,
                        compute_factor, solve_batch_streamed)
from repro.core.ovo import build_ovo_tasks
from repro.core.solver_stream import Stage2StreamStats, merge_stream_stats
from repro.core.streaming import Stage1StreamStats
from repro.core.svm import LPDSVM
from repro.core.trace import (NULL, NullTracer, ProgressPrinter, Tracer,
                              install, resolve, uninstall)
from repro.data import make_multiclass

from tests.test_stage2_mesh import run_sub


def _problem(n=240, classes=3, budget=48, C=2.0, seed=3):
    x, y = make_multiclass(n, p=5, n_classes=classes, seed=seed)
    _, labels = np.unique(y, return_inverse=True)
    fac = compute_factor(jnp.asarray(x, jnp.float32),
                         KernelParams("rbf", gamma=0.25), budget)
    tasks, _ = build_ovo_tasks(labels, classes, C)
    return np.asarray(fac.G), tasks


# ------------------------------------------------------------- recording

def test_record_span_instant_counter():
    tr = Tracer()
    t0 = tr.begin("h2d", "put")
    dt = tr.end(t0, bytes=1024)
    assert dt >= 0.0
    with tr.span("dispatch", "smo", rows=8) as sp:
        sp.set(extra=1)
    tr.instant("cache", "hit", bytes=64)
    tr.counter("queue_depth/dev0", 3)
    cats = tr.categories()
    assert cats == {"h2d": 1, "dispatch": 1, "cache": 1, "counter": 1}
    evs = tr.events()
    ph = sorted(e[0] for e in evs)
    assert ph == ["C", "X", "X", "i"]
    kern = [e for e in evs if e[1] == "dispatch"][0]
    assert kern[6] == {"rows": 8, "extra": 1}


def test_end_duration_feeds_stats_semantics():
    """`end` returns the same elapsed-seconds quantity a perf_counter pair
    would, so `put_seconds += tr.end(...)` preserves stats meanings."""
    tr = Tracer()
    before = time.perf_counter()
    t0 = tr.begin("h2d", "put")
    dt = tr.end(t0)
    ev = tr.events()[0]
    assert ev[1:3] == ("h2d", "put")
    assert ev[4] == pytest.approx(dt)
    assert before <= ev[3] <= time.perf_counter() - dt


def test_listener_sees_raw_tuples():
    tr = Tracer()
    seen = []
    tr.add_listener(seen.append)
    tr.instant("cache", "miss", bytes=7)
    assert len(seen) == 1
    assert seen[0][0] == "i" and seen[0][1] == "cache"


# ---------------------------------------------------------- export schema

def test_export_chrome_trace_schema(tmp_path):
    tr = Tracer()
    t0 = tr.begin("h2d", "put")
    tr.end(t0, bytes=int(np.int64(4096)))
    tr.instant("cache", "hit", bytes=np.int32(64))
    tr.counter("depth", np.float32(2.0))
    path = tmp_path / "t.json"
    tr.export(str(path))
    d = json.load(open(path))
    assert set(d) >= {"traceEvents"}
    evs = d["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert meta and meta[0]["name"] == "thread_name"
    spans = [e for e in evs if e["ph"] == "X"]
    assert spans
    for e in spans:
        assert {"name", "cat", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["ts"] >= 0.0
    inst = [e for e in evs if e["ph"] == "i"]
    assert inst and inst[0]["s"] == "t"
    # numpy attrs must have degraded to plain JSON numbers
    assert spans[0]["args"]["bytes"] == 4096
    ctr = [e for e in evs if e["ph"] == "C"][0]
    assert ctr["args"]["value"] == 2.0


def test_export_thread_rows(tmp_path):
    tr = Tracer()
    tr.instant("cache", "main")

    def worker():
        tr.instant("cache", "side")

    th = threading.Thread(target=worker, name="worker/devX")
    th.start()
    th.join()
    path = tmp_path / "t.json"
    tr.export(str(path))
    d = json.load(open(path))
    names = {e["args"]["name"] for e in d["traceEvents"] if e["ph"] == "M"}
    assert "worker/devX" in names
    tids = {e["tid"] for e in d["traceEvents"] if e["ph"] == "i"}
    assert len(tids) == 2


# ------------------------------------------------------------- aggregation

def _synthetic_span(tr, cat, name, t_abs, dur, tid_thread=None, **attrs):
    """Record a span with controlled geometry (optionally from a named
    thread, so that it carries a distinct tid)."""
    if tid_thread is None:
        tr._record("X", cat, name, t_abs, dur, attrs)
        return
    th = threading.Thread(
        target=lambda: tr._record("X", cat, name, t_abs, dur, attrs),
        name=tid_thread)
    th.start()
    th.join()


def test_summary_reports_figures():
    tr = Tracer()
    _synthetic_span(tr, "h2d", "put", 0.0, 1.0, bytes=10**9)
    _synthetic_span(tr, "dispatch", "smo", 0.5, 1.5, tid_thread="w0",
                    rows=1000)
    s = tr.summary()
    assert "effective H2D" in s
    assert "rows/s" in s
    assert "dispatch" in s and "2 threads" in s


def test_progress_printer_line():
    buf = io.StringIO()
    pp = ProgressPrinter(stream=buf)
    tr = Tracer()
    tr.add_listener(pp)
    t0 = tr.begin("epoch", "cheap")
    tr.end(t0, epoch=3, kind="cheap", bytes=10**6, hit_bytes=3,
           miss_bytes=1, rows=100, active=42, viol=0.25)
    line = buf.getvalue()
    assert "epoch    3" in line and "[cheap]" in line
    assert "active=      42" in line and "hit=75.0%" in line
    # non-epoch events must not print
    tr.instant("cache", "hit")
    assert buf.getvalue() == line


# ------------------------------------------------------ disabled-mode no-op

def test_null_tracer_records_nothing_and_still_times():
    t0 = NULL.begin("h2d", "put")
    assert isinstance(t0, float)
    dt = NULL.end(t0, bytes=1)
    assert isinstance(dt, float) and dt >= 0.0
    with NULL.span("dispatch", "smo") as sp:
        sp.set(rows=1)
    NULL.instant("cache", "hit")
    NULL.counter("q", 1)
    assert not NULL.enabled


def test_resolve_precedence():
    assert resolve(None) is NULL
    tr = Tracer()
    install(tr)
    try:
        assert resolve(None) is tr
        other = Tracer()
        assert resolve(other) is other
    finally:
        uninstall()
    assert resolve(None) is NULL


def test_uninstalled_spy_records_zero_events():
    """A live tracer that is neither installed nor passed must see NOTHING
    from a full streamed solve — proof the default path is the no-op."""
    spy = Tracer()
    G, tasks = _problem()
    cfg = StreamConfig(tile_rows=64)
    solve_batch_streamed(jnp.asarray(G), tasks, SolverConfig(tol=1e-2),
                         stream_config=cfg)
    assert spy.n_events == 0


def test_traced_solve_bit_identical_to_untraced():
    """Tracing observes the pipeline; it must not steer it — with both
    sinks on, the in-memory record and the profiler annotations."""
    G, tasks = _problem()
    cfg0 = StreamConfig(tile_rows=64)
    res0, st0 = solve_batch_streamed(jnp.asarray(G), tasks,
                                     SolverConfig(tol=1e-2),
                                     stream_config=cfg0, return_stats=True)
    tr = Tracer()
    cfg1 = StreamConfig(tile_rows=64, trace=tr)
    res1, st1 = solve_batch_streamed(jnp.asarray(G), tasks,
                                     SolverConfig(tol=1e-2),
                                     stream_config=cfg1, return_stats=True)
    assert tr.n_events > 0
    assert np.array_equal(np.asarray(res0.alpha), np.asarray(res1.alpha))
    assert np.array_equal(np.asarray(res0.w), np.asarray(res1.w))
    assert np.array_equal(np.asarray(res0.epochs), np.asarray(res1.epochs))
    assert st0.bytes_h2d == st1.bytes_h2d
    assert st0.epoch_bytes == st1.epoch_bytes
    assert (st0.h2d_puts, st0.d2h_syncs) == (st1.h2d_puts, st1.d2h_syncs)


# ----------------------------------------------------- derived-rate dedup

def test_stage1_stats_properties():
    st = Stage1StreamStats(bytes_h2d=2 * 10**9, put_seconds=1.0,
                           drain_seconds=1.0, seconds=4.0)
    assert st.h2d_gbps == pytest.approx(2.0)
    assert st.overlap_efficiency == pytest.approx(0.5)
    assert Stage1StreamStats().overlap_efficiency == 0.0


def test_stage2_stats_properties():
    st = Stage2StreamStats(bytes_put=3 * 10**9, put_seconds=2.0,
                           drain_seconds=1.0, seconds=10.0)
    assert st.h2d_gbps == pytest.approx(1.5)
    assert st.overlap_efficiency == pytest.approx(0.7)
    # fully busy clamps at 0, never negative
    st2 = Stage2StreamStats(put_seconds=9.0, drain_seconds=9.0, seconds=1.0)
    assert st2.overlap_efficiency == 0.0


# ------------------------------------------------------- pipeline coverage

def test_streamed_solve_emits_pipeline_spans():
    G, tasks = _problem()
    tr = Tracer()
    cfg = StreamConfig(tile_rows=64, trace=tr)
    _, st = solve_batch_streamed(jnp.asarray(G), tasks, SolverConfig(tol=1e-2),
                                 stream_config=cfg, return_stats=True)
    cats = tr.categories()
    for want in ("h2d", "dispatch", "d2h", "engine", "epoch"):
        assert cats.get(want, 0) > 0, cats
    assert "kernel" not in cats and "drain" not in cats
    assert not any(e[0] == "C" for e in tr.events())
    # span durations ARE the stats: the h2d spans sum to put_seconds
    h2d = sum(e[4] for e in tr.events()
              if e[0] == "X" and e[1] == "h2d")
    assert h2d == pytest.approx(st.put_seconds, rel=1e-6)


def test_fit_trace_kwarg_records_both_stages():
    x, y = make_multiclass(200, p=5, n_classes=3, seed=1)
    tr = Tracer()
    svm = LPDSVM(KernelParams("rbf", gamma=0.25), C=2.0, budget=48,
                 stream=True, stream_config=StreamConfig(tile_rows=64,
                                                         chunk_rows=64))
    svm.fit(x, y, trace=tr)
    cats = tr.categories()
    assert cats.get("fit", 0) == 2          # stage1 + stage2 spans
    assert cats.get("read", 0) > 0          # stage-1 chunk staging
    assert cats.get("h2d", 0) > 0
    names = {e[2] for e in tr.events() if e[1] == "fit"}
    assert names == {"stage1", "stage2"}


def test_fit_trace_without_stream_config_covers_polish():
    """An explicit fit(trace=) with NO StreamConfig must still record both
    stage spans and the polish ladder levels (tracer threading must not
    depend on a stream config existing)."""
    x, y = make_multiclass(200, p=5, n_classes=3, seed=2)
    tr = Tracer()
    svm = LPDSVM(KernelParams("rbf", gamma=0.25), C=2.0, budget=48,
                 polish=True, polish_levels=2)
    svm.fit(x, y, trace=tr)
    fit_names = {e[2] for e in tr.events() if e[1] == "fit"}
    assert fit_names == {"stage1", "stage2"}
    levels = [e[2] for e in tr.events() if e[1] == "polish"]
    assert levels == [f"level_{i}" for i in range(len(levels))] and levels


# ------------------------------------------------------- profiler mirror

class _Marks:
    """Stand-in for `jax.profiler.TraceAnnotation` that logs each enter and
    exit by name."""

    log = []

    def __init__(self, name, **kwargs):
        assert not kwargs              # attrs must never reach the name
        self.name = name

    def __enter__(self):
        _Marks.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        _Marks.log.append(("exit", self.name))
        return False


@pytest.fixture
def marks(monkeypatch):
    import jax.profiler
    _Marks.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Marks)
    return _Marks.log


def test_mirror_opens_one_annotation_per_span_nested(marks):
    tr = Tracer()
    t0 = tr.begin("engine", "feed_block")
    with tr.span("dispatch", "smo", rows=3):
        pass
    t1 = tr.begin("h2d", "put_vec")
    tr.end(t1, bytes=64)
    tr.end(t0)
    tr.instant("cache", "hit")
    tr.counter("depth", 1)
    assert marks == [("enter", "engine/feed_block"),
                     ("enter", "dispatch/smo"), ("exit", "dispatch/smo"),
                     ("enter", "h2d/put_vec"), ("exit", "h2d/put_vec"),
                     ("exit", "engine/feed_block")]
    # The in-memory record is unchanged by the mirror: attrs live there.
    assert tr.categories() == {"engine": 1, "dispatch": 1, "h2d": 1,
                               "cache": 1, "counter": 1}
    put = [e for e in tr.events() if e[2] == "put_vec"][0]
    assert put[6] == {"bytes": 64}


def test_mirror_only_tracer_keeps_nothing(marks):
    tr = Tracer(keep=False)
    assert tr.enabled
    seen = []
    tr.add_listener(seen.append)
    t0 = tr.begin("d2h", "block_drain")
    assert tr.end(t0, bytes=8) >= 0.0
    tr.instant("cache", "miss")
    assert tr.n_events == 0 and tr.events() == []
    assert marks == [("enter", "d2h/block_drain"), ("exit", "d2h/block_drain")]
    assert [e[1] for e in seen] == ["d2h", "cache"]


def test_null_tracer_opens_no_annotation(marks):
    NULL.end(NULL.begin("h2d", "put_vec"))
    with NULL.span("dispatch", "smo"):
        pass
    assert marks == []


def test_streamed_solve_mirrors_every_span(marks):
    """Each in-memory span of a streamed solve has its annotation."""
    G, tasks = _problem()
    tr = Tracer()
    solve_batch_streamed(jnp.asarray(G), tasks, SolverConfig(tol=1e-2),
                         stream_config=StreamConfig(tile_rows=64, trace=tr))
    spans = collections.Counter(f"{e[1]}/{e[2]}" for e in tr.events()
                                if e[0] == "X")
    opened = collections.Counter(n for k, n in marks if k == "enter")
    assert opened == spans
    assert collections.Counter(n for k, n in marks if k == "exit") == spans
    for name in ("h2d/put_vec", "h2d/put_block", "dispatch/smo",
                 "dispatch/window", "dispatch/row_sq", "d2h/block_drain",
                 "d2h/violation", "d2h/result", "engine/feed_block",
                 "engine/end_pass", "compact/recompact", "epoch/full"):
        assert spans[name] > 0, name


# ------------------------------------------------ round-trip counters

def _binary_problem(n=300, budget=32, seed=5):
    x, y = make_multiclass(n, p=4, n_classes=2, seed=seed)
    _, labels = np.unique(y, return_inverse=True)
    fac = compute_factor(jnp.asarray(x, jnp.float32),
                         KernelParams("rbf", gamma=0.5), budget)
    tasks, _ = build_ovo_tasks(labels, 2, 1.0)
    return np.asarray(fac.G), tasks


@pytest.mark.parametrize("wire,puts_per_block", [("f32", 1), ("int8", 2)])
def test_put_and_sync_counts_match_a_hand_count(wire, puts_per_block):
    """One task over every row: each SMO window puts 5 vectors and is
    drained by 2 reads; each G block put is 1 put (int8: codes and scales);
    each full pass reads one violation per block; the result reads W."""
    G, tasks = _binary_problem()
    tile = 64
    _, st = solve_batch_streamed(
        G, tasks, SolverConfig(tol=1e-3),
        stream_config=StreamConfig(tile_rows=tile, block_dtype=wire),
        return_stats=True)
    n_blocks = -(-G.shape[0] // tile)
    assert st.full_passes >= 2 and st.kernel_calls > n_blocks
    assert st.h2d_puts == (5 * st.kernel_calls
                           + puts_per_block * st.blocks_streamed)
    assert st.d2h_syncs == (2 * st.kernel_calls
                            + st.full_passes * n_blocks + 1)


def test_merge_sums_round_trip_counters():
    a = Stage2StreamStats(h2d_puts=7, d2h_syncs=3)
    b = Stage2StreamStats(h2d_puts=5, d2h_syncs=2)
    m = merge_stream_stats(Stage2StreamStats(), [a, b], seconds=1.0,
                           n_devices=2)
    assert (m.h2d_puts, m.d2h_syncs) == (12, 5)


# ------------------------------------------------- 2-device farm (subprocess)

FARM_CODE = r"""
import json
import numpy as np
import jax
import jax.numpy as jnp
from repro.core import (KernelParams, SolverConfig, StreamConfig,
                        compute_factor, solve_tasks_streamed)
from repro.core.ovo import build_ovo_tasks
from repro.core.trace import Tracer
from repro.data import make_multiclass

x, y = make_multiclass(300, p=5, n_classes=4, seed=7)
_, labels = np.unique(y, return_inverse=True)
fac = compute_factor(jnp.asarray(x, jnp.float32),
                     KernelParams("rbf", gamma=0.25), 48)
tasks, _ = build_ovo_tasks(labels, 4, 2.0)
tr = Tracer()
cfg = StreamConfig(tile_rows=64, trace=tr)
solve_tasks_streamed(np.asarray(fac.G), tasks, SolverConfig(tol=1e-2),
                     devices=jax.local_devices(), stream_config=cfg,
                     overlap=True)
tr.export("/tmp/_trace_farm_test.json")
d = json.load(open("/tmp/_trace_farm_test.json"))
evs = d["traceEvents"]
names = sorted({e["args"]["name"] for e in evs if e["ph"] == "M"})
span_tids = sorted({e["tid"] for e in evs if e["ph"] == "X"})
cats = sorted({e["cat"] for e in evs if e["ph"] == "X"})
print("NAMES:" + json.dumps(names))
print("TIDS:%d" % len(span_tids))
print("CATS:" + json.dumps(cats))
print("SUMMARY_OK:%d" % ("rows/s" in tr.summary()))
"""


def test_farm_trace_covers_all_threads():
    """Under the real 2-device farm the trace must carry spans from the
    shared reader (main thread) AND every device worker thread, with the
    queue/backpressure category present — the lock survives concurrency."""
    out = run_sub(FARM_CODE, n_dev=2)
    lines = dict(ln.split(":", 1) for ln in out.strip().splitlines()
                 if ":" in ln)
    names = json.loads(lines["NAMES"])
    assert "worker/dev0" in names and "worker/dev1" in names
    assert int(lines["TIDS"]) >= 3
    cats = json.loads(lines["CATS"])
    for want in ("read", "h2d", "dispatch", "d2h", "queue", "epoch"):
        assert want in cats, cats
    assert lines["SUMMARY_OK"] == "1"
