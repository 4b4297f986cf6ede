"""The trace reduction on hand-built events with hand-computed answers,
and on a small trace recorded here on the CPU."""
import os

import pytest

from bench import trace_reduce as tr

E = tr.Event


def test_union_clip_and_complement():
    busy = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert tr.clip(busy, 1, 6) == [(1, 3), (5, 6)]
    assert tr.complement(tr.clip(busy, 1, 10), 1, 10) == [(3, 5), (8, 10)]


def test_reduce_hand_built():
    # Window 0..100 ns.  Device 0 runs programs at 10..30 and 25..40 (busy
    # 30 ns), device 1 at 50..90 (busy 40 ns): busy averages 35 ns.
    host = [E("window", 0, 100), E("stage1", 0, 45), E("stage2", 45, 50),
            E("between_jobs", 95, 5), E("unrelated", 0, 100)]
    d0 = tr.DevicePlane("/device:TPU:0",
                        modules=[E("jit_a(1)", 10, 20), E("jit_b(2)", 25, 15)],
                        ops=[E("fusion.1", 10, 20), E("smo", 25, 10),
                             E("smo", 35, 5)])
    d1 = tr.DevicePlane("/device:TPU:1", modules=[], ops=[E("dot", 50, 40)])
    out = tr.reduce_events([d0, d1], host)
    assert out["busy_s"] == pytest.approx(35e-9)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["modules"] == pytest.approx({"jit_a": 20e-9, "jit_b": 15e-9})
    assert out["ops"] == pytest.approx({"fusion.1": 20e-9, "smo": 15e-9,
                                        "dot": 40e-9})
    # Device 0 idles 0..10 (stage1), 40..100: midpoint 70 is in stage2.
    # Device 1 idles 0..50 (midpoint 25: stage1) and 90..100 (midpoint 95:
    # between_jobs, the shortest span holding it).
    gaps = out["gaps"]
    assert gaps[0] == ("stage2", pytest.approx(60e-9))
    assert gaps[1] == ("stage1", pytest.approx(50e-9))
    assert sorted(g[1] for g in gaps) == pytest.approx(
        [10e-9, 10e-9, 50e-9, 60e-9])
    assert out["idle_by_span"] == pytest.approx(
        {"stage1": 60e-9, "stage2": 60e-9, "between_jobs": 10e-9})
    assert out["breakdown"]["device_ops"][0] == ["dot", pytest.approx(40e-9)]
    assert len(out["breakdown"]["idle_gaps"]) == 4


def test_modules_line_wins_over_ops_for_busy_time():
    host = [E("window", 0, 100)]
    # Ops nested inside one module execution must not add busy time.
    d = tr.DevicePlane("/device:TPU:0", modules=[E("jit_step(7)", 0, 50)],
                       ops=[E("op", i, 1) for i in range(0, 50, 2)]
                       + [E("late", 60, 10)])
    assert tr.reduce_events([d], host)["busy_s"] == pytest.approx(50e-9)


def test_no_window_or_device_is_an_error():
    d = tr.DevicePlane("/device:TPU:0", modules=[E("m", 0, 1)], ops=[])
    with pytest.raises(ValueError):
        tr.reduce_events([d], [E("stage1", 0, 10)])
    with pytest.raises(ValueError):
        tr.reduce_events([], [E("window", 0, 10)])


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: (a @ a).sum())
    a = jnp.ones((128, 128))
    f(a).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("stage1"):
            f(a).block_until_ready()
        with jax.profiler.TraceAnnotation("between_jobs"):
            pass
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    assert os.path.getsize(path) > 0
    planes, spans = tr.load(path)
    assert planes == []                       # no TPU plane on the CPU
    names = {s.name for s in spans}
    assert {"window", "stage1", "between_jobs"} <= names
    win = next(s for s in spans if s.name == "window")
    st1 = next(s for s in spans if s.name == "stage1")
    assert win.start_ns <= st1.start_ns and st1.end_ns <= win.end_ns
    # The host spans reduce against a device plane built by hand.
    dev = tr.DevicePlane("/device:TPU:0", ops=[],
                         modules=[E("jit_f(1)", st1.start_ns, st1.dur_ns)])
    out = tr.reduce_events([dev], spans)
    assert out["busy_s"] == pytest.approx(min(st1.dur_ns, win.dur_ns) * 1e-9)
    assert out["window_s"] == pytest.approx(win.dur_ns * 1e-9)
