"""The program-span split of `bench/program_spans.py` on hand-built events
with hand-computed answers and on a traced window recorded here on the
CPU, and the untraced benchmark run's promise: no program tracer, no
program span."""
import jax
import pytest

from bench import job, run
from bench import program_spans as ps
from bench import trace_reduce as tr
from bench.tests.test_bench_rehearsal import TINY

E = tr.Event


def _plane(*busy):
    return tr.DevicePlane("/device:TPU:0", ops=[],
                          modules=[E(f"jit_p({i})", s, d)
                                   for i, (s, d) in enumerate(busy)])


BENCH_SPANS = [E("window", 0, 200), E("stage1", 0, 50), E("stage2", 50, 120),
               E("predict", 170, 20), E("between_jobs", 190, 10)]


def test_gap_is_split_exactly_across_nested_program_spans():
    # Device idle 60..100 inside stage2.  fit/stage2 covers 55..165; inside
    # it engine/feed_block 60..90 holds h2d/put_vec 62..70 and dispatch/smo
    # 70..74; d2h/block_drain 90..96.  Self time of each span over the gap:
    # feed_block 2 + 16 = 18, put_vec 8, smo 4, block_drain 6, fit 4.
    program = [E("fit/stage2", 55, 110), E("engine/feed_block", 60, 30),
               E("h2d/put_vec", 62, 8), E("dispatch/smo", 70, 4),
               E("d2h/block_drain", 90, 6)]
    planes = [_plane((0, 60), (100, 100))]
    out = ps.reduce_events(planes, BENCH_SPANS + program)
    idle = out["by_category"]
    assert idle == pytest.approx({"engine": 18e-9, "h2d": 8e-9,
                                  "dispatch": 4e-9, "d2h": 6e-9,
                                  "fit": 4e-9, "unspanned": 0.0})
    assert sum(idle.values()) == pytest.approx(40e-9)
    assert out["program_spans"] == 5
    assert out["program_span_s"] == pytest.approx({
        "fit/stage2": 110e-9, "engine/feed_block": 30e-9,
        "h2d/put_vec": 8e-9, "dispatch/smo": 4e-9, "d2h/block_drain": 6e-9})
    assert dict(out["idle_by_program_span"]) == pytest.approx({
        "engine/feed_block": 18e-9, "h2d/put_vec": 8e-9,
        "d2h/block_drain": 6e-9, "dispatch/smo": 4e-9,
        "fit/stage2": 4e-9})


def test_unspanned_holds_stage2_idle_no_program_span_covers():
    # Idle 60..100 (stage2) and 180..200 (predict, between_jobs).  Program
    # spans cover 70..80 of the first gap and all of the second.
    program = [E("h2d/put_vec", 70, 10), E("predict/vote", 180, 20)]
    planes = [_plane((0, 60), (100, 80))]
    out = ps.reduce_events(planes, BENCH_SPANS + program)
    # Only stage2's idle is split by category; predict's is not in it.
    assert out["by_category"] == pytest.approx({"h2d": 10e-9,
                                                "unspanned": 30e-9})
    assert dict(out["idle_by_program_span"]) == pytest.approx(
        {"predict/vote": 20e-9, "h2d/put_vec": 10e-9})


def test_split_is_per_device_and_lists_idle_free_categories():
    # Two planes: idle 60..100 and 80..100; a dispatch span covers 60..100
    # and an h2d span lies where both devices are busy.
    program = [E("dispatch/smo", 60, 40), E("h2d/put_vec", 120, 10)]
    planes = [_plane((0, 60), (100, 100)),
              tr.DevicePlane("/device:TPU:1", ops=[],
                             modules=[E("jit_q(1)", 0, 80),
                                      E("jit_q(1)", 100, 100)])]
    out = ps.reduce_events(planes, BENCH_SPANS + program)
    assert out["by_category"] == pytest.approx(
        {"dispatch": 30e-9, "h2d": 0.0, "unspanned": 0.0})


def test_no_window_or_device_is_an_error():
    with pytest.raises(ValueError):
        ps.reduce_events([_plane((0, 1))], [E("stage2", 0, 10)])
    with pytest.raises(ValueError):
        ps.reduce_events([], [E("window", 0, 10)])


def test_program_spans_leave_the_benchmark_reduction_unchanged():
    """Mirrored spans in the host list move none of the numbers the
    benchmark's own reduction reports."""
    d0 = tr.DevicePlane("/device:TPU:0",
                        modules=[E("jit_a(1)", 10, 20), E("jit_b(2)", 25, 15),
                                 E("jit_smo_epoch_pallas(3)", 120, 30)],
                        ops=[E("fusion.1", 10, 20), E("smo", 120, 30)])
    program = [E("fit/stage2", 52, 110), E("engine/feed_block", 60, 30),
               E("h2d/put_vec", 62, 8), E("d2h/block_drain", 90, 6),
               E("stage1/gram", 5, 10), E("predict/vote", 175, 10),
               E("odd/span", 0, 200)]
    assert (tr.reduce_events([d0], BENCH_SPANS + program)
            == tr.reduce_events([d0], BENCH_SPANS))
    # Stage2 (50..170) is idle 50..120 and 150..170: 90 ns, none spanned
    # without program spans.
    bare = ps.reduce_events([d0], BENCH_SPANS)
    assert bare["by_category"] == {"unspanned": pytest.approx(90e-9)}
    assert bare["idle_by_program_span"] == []


def test_innermost_segments_are_the_spans_self_time():
    spans = [E("a/outer", 0, 100), E("b/inner", 10, 20), E("c/deep", 15, 5),
             E("d/late", 90, 30)]
    assert ps.innermost_segments(spans) == [
        (0, 10, "a/outer"), (10, 15, "b/inner"), (15, 20, "c/deep"),
        (20, 30, "b/inner"), (30, 90, "a/outer"), (90, 120, "d/late")]
    assert ps.innermost_segments([]) == []


def test_intersect_sorted_interval_lists():
    assert ps.intersect([(0, 10), (20, 30)], [(5, 25), (28, 40)]) == [
        (5, 10), (20, 25), (28, 30)]
    assert ps.intersect([(0, 1)], []) == []


def test_program_span_names():
    for ok in ("h2d/put_vec", "d2h/block_drain", "epoch/full",
               "stage1/eig_projector"):
        assert ps.PROGRAM_SPAN.match(ok), ok
    for no in ("window", "stage2", "/jax/core/compile", "a/b/c",
               "jit_f/fusion.1", "h2d/put_vec#bytes=8#"):
        assert not ps.PROGRAM_SPAN.match(no), no


IDLE = {"h2d": 1.5, "d2h": 6.0, "dispatch": 0.5, "engine": 0.75,
        "compact": 0.25, "fit": 0.5, "unspanned": 0.25}


@pytest.mark.parametrize("group,expect", [
    ("h2d", 1.5), ("d2h", 6.0), ("dispatch", 0.5), ("host", 1.5),
    ("unspanned", 0.25)])
def test_exposed_groups_the_categories(group, expect):
    assert ps.exposed(IDLE)[group] == pytest.approx(expect)


def test_exposed_without_program_spans_is_all_unspanned():
    assert ps.exposed({"unspanned": 4.0}) == {
        "h2d": 0.0, "d2h": 0.0, "dispatch": 0.0, "unspanned": 4.0,
        "host": 0.0}


def test_summarize_per_job():
    program = [E("fit/stage2", 55, 110), E("h2d/put_vec", 62, 8),
               E("dispatch/smo", 70, 4), E("d2h/block_drain", 90, 6)]
    jobs = [{"seconds": 1.0, "stage2_s": 0.5, "smo_calls": 3,
             "h2d_puts": 20, "d2h_syncs": 8},
            {"seconds": 3.0, "stage2_s": 1.5, "smo_calls": 5,
             "h2d_puts": 30, "d2h_syncs": 10}]
    out = ps.summarize([_plane((0, 60), (100, 100))], BENCH_SPANS + program,
                       jobs)
    assert out["jobs"] == 2 and out["job_s"] == 2.0
    assert (out["smo_calls"], out["h2d_puts"], out["d2h_syncs"]) == (4, 25, 9)
    # Idle 60..100: fit/stage2's self time 2 + 16 + 4 = 22 ns (host), h2d
    # 8, dispatch 4, d2h 6; per job half of each.
    assert out["exposed_s"] == pytest.approx({
        "h2d": 4e-9, "d2h": 3e-9, "dispatch": 2e-9, "host": 11e-9,
        "unspanned": 0.0})
    assert out["stage2_idle_s"] == pytest.approx(20e-9)
    assert out["unspanned_share"] == 0.0
    assert out["busy_s"] == pytest.approx(160e-9)
    assert list(out["program_span_s"])[0] == "fit/stage2"


def test_streamed_cell_traced_on_the_cpu(tmp_path):
    """A tiny ``susy.stream`` window profiled with the mirroring tracer:
    the program's spans are on the host plane, inside ``stage2``, named
    without metadata; the counters are read; the tracer is uninstalled."""
    from repro.core import trace as program_trace

    cfg, traffic = TINY["susy.stream"]
    jobs = ps.traced_window("susy.stream", 2 ** 31 + 99, 0.0,
                            jax.devices()[:1], str(tmp_path),
                            config_overrides=cfg, traffic_overrides=traffic)
    assert program_trace.active() is None
    assert len(jobs) == 1
    assert jobs[0]["h2d_puts"] > 0 and jobs[0]["d2h_syncs"] > 0
    path = tr.find_xplane(str(tmp_path))
    _, bench_spans = tr.load(path)
    spans = ps.load(path)
    assert spans and not any("#" in s.name for s in spans)
    st2 = next(s for s in bench_spans if s.name == "stage2")
    inside = {s.name for s in spans
              if st2.start_ns <= s.start_ns and s.end_ns <= st2.end_ns}
    assert {"h2d/put_vec", "dispatch/smo", "d2h/block_drain", "fit/stage2",
            "engine/feed_block", "epoch/full"} <= inside
    assert {"predict/features", "d2h/decisions", "predict/vote"} <= {
        s.name for s in spans}
    # Against a hand-built device plane the stage-2 idle is all spanned
    # but for the instants between the program's spans.
    dev = tr.DevicePlane("/device:TPU:0", ops=[], modules=[])
    out = ps.reduce_events([dev], bench_spans + spans)
    assert out["by_category"]["h2d"] > 0 and out["by_category"]["dispatch"] > 0
    assert out["by_category"]["unspanned"] < 0.1 * sum(
        out["by_category"].values())


def test_untraced_run_installs_no_tracer_and_opens_no_program_span(
        monkeypatch):
    from repro.core import trace as program_trace
    opened = []
    real = jax.profiler.TraceAnnotation

    class Counting(real):
        def __init__(self, name, **kw):
            opened.append(name)
            super().__init__(name, **kw)

    seen = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    monkeypatch.setattr(job, "make_svm", _spy(job.make_svm, seen))
    cfg, traffic = TINY["susy.stream"]
    out = run.run_cell("susy.stream", 2 ** 31 + 7, 0.0, False,
                       jax.devices()[:1], config_overrides=cfg,
                       traffic_overrides=traffic)
    assert out["correct"], out["checks"]
    assert seen and all(a is None for a in seen)
    assert program_trace.active() is None
    assert "window" in opened and "stage2" in opened
    assert not [n for n in opened if "/" in n]
    # Untraced, the run reports the end-to-end metrics alone.
    assert set(out["metrics"]) == {"job_s", "setup_s"}


def _spy(make_svm, seen):
    """Wrap `job.make_svm` to note the installed tracer at each job."""
    from repro.core import trace as program_trace

    def wrapped(*args, **kwargs):
        seen.append(program_trace.active())
        return make_svm(*args, **kwargs)
    return wrapped
