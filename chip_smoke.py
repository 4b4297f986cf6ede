#!/usr/bin/env python3
"""Chip smoke test: the two-stage LPD-SVM fit at MNIST-8M's width on a TPU.

    python chip_smoke.py [--seed 0]      # one chip: Fit A and Fit B
    python chip_smoke.py --chips 4       # four chips: the stage-2 farm only

One job at MNIST-8M's published shape (784 features, 10 classes, so 45
one-vs-one tasks), generated from ``--seed`` by `data.make_multiclass`, RBF
kernel at the median gamma, Nystrom budget B = 2048.  MNIST-8M's 8.1M rows
are cut to 131,072 train and 16,384 test rows so that one run fits the time
limit; features, classes and B are not cut.

One chip, through `LPDSVM.fit` / `predict`:
  * Fit A, the default route: device-resident stage 1 and `solve_batch`;
  * Fit B, the streamed route on one device: ``stream=True`` with an int8
    stage-1 wire, which runs the Pallas int8 gram and the Pallas SMO kernel.
Both fits predict the test set and are checked against each other, and
Fit A's stage 1 and decisions are recomputed on the host CPU for a few rows
as an f32 reference.

``--chips 4`` solves Fit B's stage 2 with the multi-device task farm
(`solve_streamed_auto`) and again with `solve_batch_streamed` on device 0,
and compares the two, on 32,768 train rows.

Exits non-zero, printing no result, when JAX finds no TPU or any check
fails.  The last line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# -- the job: MNIST-8M's width, rows cut to one run ---------------------------
FEATURES = 784
CLASSES = 10
BUDGET = 2048
TRAIN_ROWS = 131_072          # MNIST-8M has 8.1M
TEST_ROWS = 16_384
FARM_TRAIN_ROWS = 32_768      # --chips 4: cut further, to bound its run time
SEP, WITHIN = 0.1, 0.08       # class and sub-cluster spread: test error > 0
C = 1.0
TOL = 1e-2                    # stage-2 stopping tolerance (max KKT violation)
MAX_EPOCHS = 2000

# -- tolerances of the checks -------------------------------------------------
AGREE_MIN = 0.99      # share of test rows that Fit A and Fit B label alike
ERR_TOL = 0.005       # |test error A - test error B|
DEC_TOL = 0.05        # RMS(decision A - decision B) / RMS(decision A)
REF_ROWS = 256        # rows recomputed on the host CPU in f32
REF_TOL = 1e-3        # chip vs host CPU: max |difference| / RMS(reference)
FARM_RTOL, FARM_ATOL = 1e-4, 1e-5   # farm vs one device, alpha and w

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading a
    compiled program from the persistent cache), summed from its own
    monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **_):
        if event in COMPILE_EVENTS:
            self.seconds += secs


class Checks:
    """Named pass/fail results, printed as they are made."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str) -> None:
        print(f"check {name}: {'ok' if ok else 'FAILED'} ({detail})",
              flush=True)
        if not ok:
            self.failed.append(name)


def make_job(seed: int, n_train: int, n_test: int):
    """Train/test split of the generated job and its RBF kernel."""
    import numpy as np
    from repro.core import KernelParams, median_gamma
    from repro.data import make_multiclass

    x, y = make_multiclass(n_train + n_test, p=FEATURES, n_classes=CLASSES,
                           sep=SEP, within=WITHIN, seed=seed)
    kernel = KernelParams("rbf", gamma=median_gamma(x[:n_train], seed=seed))
    return (np.ascontiguousarray(x[:n_train]), y[:n_train],
            np.ascontiguousarray(x[n_train:]), y[n_train:], kernel)


def make_svm(kernel, seed: int, streamed: bool, budget: int = BUDGET):
    from repro.core import LPDSVM, StreamConfig
    kw = (dict(stream=True, stream_config=StreamConfig(stage1_dtype="int8"))
          if streamed else {})
    return LPDSVM(kernel, C=C, budget=budget, tol=TOL, max_epochs=MAX_EPOCHS,
                  seed=seed, **kw)


def rel_max(a, ref) -> float:
    import numpy as np
    a, ref = np.asarray(a, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(a - ref)) / np.sqrt(np.mean(ref ** 2)))


def check_tpu_route(check: Checks) -> None:
    """The TPU choices on the streamed path were taken: compiled Pallas
    kernels, not interpret mode and not the jnp oracles."""
    import jax
    import jax.numpy as jnp
    from repro.core.solver_stream import default_epoch_fn
    from repro.core.streaming import default_gram_q8_fn
    from repro.kernels import ops

    check("pallas_compiled", not ops._default_interpret(),
          f"interpret mode {ops._default_interpret()}")
    check("smo_kernel_chosen", default_epoch_fn() is ops.smo_epoch,
          f"epoch fn {default_epoch_fn().__name__}")
    check("gram_q8_kernel_chosen", default_gram_q8_fn() is ops.gram_q8,
          f"int8 gram fn {default_gram_q8_fn().__name__}")
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    col = f32((256,))
    text = jax.jit(functools.partial(ops.smo_epoch, full_pass=True)).lower(
        f32((256, BUDGET)), col, col, col, col,
        jax.ShapeDtypeStruct((256,), jnp.int32), f32((BUDGET,))).as_text()
    check("smo_lowered_to_mosaic", "tpu_custom_call" in text,
          "tpu_custom_call in the lowered SMO epoch")


def cpu_reference(svm, x_train, x_test, dec_test, check: Checks) -> None:
    """Recompute Fit A's rank, G rows and decisions for REF_ROWS rows on the
    host CPU in f32, with the fitted landmarks, projector and weights."""
    import jax
    import numpy as np
    from repro.core.kernel_fn import gram
    from repro.core.nystrom import DEFAULT_EIG_RTOL, _eig_projector
    from repro.core.ovo import ovo_decision_values

    cpu = jax.devices("cpu")[0]
    put = lambda a: jax.device_put(np.asarray(a, np.float32), cpu)
    fac, kp = svm.factor, svm.kernel
    lm, proj = put(fac.landmarks), put(fac.projector)
    _, _, rank = _eig_projector(gram(lm, lm, kp), kp, DEFAULT_EIG_RTOL)
    check("rank_vs_cpu", int(rank) == fac.effective_rank,
          f"chip {fac.effective_rank}, host CPU {int(rank)}")
    g_ref = gram(put(x_train[:REF_ROWS]), lm, kp) @ proj
    err = rel_max(np.asarray(fac.G[:REF_ROWS]), g_ref)
    check("G_vs_cpu", err <= REF_TOL, f"{err:.3g} <= {REF_TOL}")
    d_ref = ovo_decision_values(gram(put(x_test[:REF_ROWS]), lm, kp) @ proj,
                                put(svm.W_))
    err = rel_max(dec_test[:REF_ROWS], d_ref)
    check("decisions_vs_cpu", err <= REF_TOL, f"{err:.3g} <= {REF_TOL}")


def run_fits(seed: int, check: Checks, *, n_train: int = TRAIN_ROWS,
             n_test: int = TEST_ROWS, budget: int = BUDGET) -> None:
    """Fit A and Fit B on one device, predict, compare."""
    import numpy as np

    clock = CompileClock()
    t0 = time.perf_counter()
    x, y, xt, yt, kernel = make_job(seed, n_train, n_test)
    print(f"job: {n_train} train / {n_test} test rows, p={FEATURES}, "
          f"{CLASSES} classes, B={budget}, gamma={kernel.gamma:.6g}, C={C}, "
          f"tol={TOL}; made in {time.perf_counter() - t0:.3f} s", flush=True)
    out = {}
    for name, streamed in (("A", False), ("B", True)):
        svm = make_svm(kernel, seed, streamed, budget)
        c0 = clock.seconds
        svm.prepare(x)
        c1 = clock.seconds
        svm.fit(x, y)
        c2 = clock.seconds
        t0 = time.perf_counter()
        dec = np.asarray(svm.decision_function(xt))
        pred = svm.predict(xt)
        c3 = clock.seconds
        pred_s = time.perf_counter() - t0
        st = svm.stats
        err = float(np.mean(pred != yt))
        route = ("streamed stage 1 (int8 wire) + streamed stage 2"
                 if streamed else "device-resident stage 1 + solve_batch")
        print(f"fit {name} [{route}]: stage1 {st.stage1_seconds:.3f} s "
              f"(compile {c1 - c0:.3f} s), stage2 {st.stage2_seconds:.3f} s "
              f"(compile {c2 - c1:.3f} s), predict {pred_s:.3f} s "
              f"(compile {c3 - c2:.3f} s); rank {st.effective_rank}, "
              f"epochs max {int(st.epochs.max())}, violation max "
              f"{float(st.violations.max()):.4g}, test error {err:.5f}",
              flush=True)
        check(f"fit{name}_route",
              st.stage1_streamed == streamed and st.stage2_streamed == streamed,
              f"stage1_streamed={st.stage1_streamed}, "
              f"stage2_streamed={st.stage2_streamed}")
        if streamed:
            s1, s2 = st.stage1_stats, st.stage2_stats
            print(f"fit B wire: stage 1 {s1.wire_dtype}, {s1.chunks} chunks; "
                  f"stage 2 {s2.n_devices} device(s), {s2.kernel_calls} "
                  f"kernel calls, tile {s2.tile_rows} rows", flush=True)
            check("fitB_kernels_ran",
                  s1.wire_dtype == "int8" and s1.chunks > 0
                  and s2.kernel_calls > 0 and s2.n_devices == 1,
                  f"int8 chunks {s1.chunks}, SMO kernel calls "
                  f"{s2.kernel_calls}, devices {s2.n_devices}")
        check(f"fit{name}_converged", float(st.violations.max()) <= TOL,
              f"max violation {float(st.violations.max()):.4g} <= {TOL}")
        check(f"fit{name}_decisions_finite",
              dec.shape == (n_test, CLASSES * (CLASSES - 1) // 2)
              and bool(np.isfinite(dec).all()), f"shape {dec.shape}")
        if not streamed:
            cpu_reference(svm, x, xt, dec, check)
        out[name] = (st.effective_rank, dec, pred, err)
        del svm

    (ra, da, pa, ea), (rb, db, pb, eb) = out["A"], out["B"]
    check("rank_A_vs_B", ra == rb, f"{ra} vs {rb}")
    agree = float(np.mean(pa == pb))
    check("labels_A_vs_B", agree >= AGREE_MIN, f"{agree:.5f} >= {AGREE_MIN}")
    rms = float(np.sqrt(np.mean((da - db) ** 2) / np.mean(da ** 2)))
    check("decisions_A_vs_B", rms <= DEC_TOL, f"{rms:.4g} <= {DEC_TOL}")
    check("error_A_vs_B", abs(ea - eb) <= ERR_TOL,
          f"|{ea:.5f} - {eb:.5f}| <= {ERR_TOL}")


def run_farm(seed: int, check: Checks, *, n_train: int = FARM_TRAIN_ROWS,
             n_test: int = TEST_ROWS, budget: int = BUDGET) -> None:
    """Fit B's G and tasks, solved by the device farm and on device 0."""
    import jax
    import numpy as np
    from repro.core import solve_batch_streamed
    from repro.core.ovo import build_ovo_tasks
    from repro.core.solver_stream import solve_streamed_auto

    x, y, _, _, kernel = make_job(seed, n_train, n_test)
    svm = make_svm(kernel, seed, True, budget)
    fac = svm.prepare(x)
    print(f"stage 1: {n_train} train rows, {svm.stats.stage1_seconds:.3f} s "
          f"on device 0 only (LPDSVM.prepare passes no devices); rank "
          f"{fac.effective_rank}", flush=True)
    _, labels = np.unique(y, return_inverse=True)
    tasks, _ = build_ovo_tasks(labels, CLASSES, C)
    res = {}
    for name, solve in (("farm", solve_streamed_auto),
                        ("one device", solve_batch_streamed)):
        t0 = time.perf_counter()
        r, st = solve(fac.G, tasks, svm.config,
                      stream_config=svm.stream_config, return_stats=True)
        print(f"stage 2 {name}: {time.perf_counter() - t0:.3f} s on "
              f"{st.n_devices} device(s), epochs max {int(r.epochs.max())}, "
              f"violation max {float(r.violation.max()):.4g}", flush=True)
        res[name] = (r, st)
    (farm, fst), (one, _) = res["farm"], res["one device"]
    check("farm_devices", fst.n_devices == len(jax.devices()),
          f"{fst.n_devices} of {len(jax.devices())}")
    for field in ("alpha", "w"):
        a, b = np.asarray(getattr(farm, field)), np.asarray(getattr(one, field))
        close = np.allclose(a, b, rtol=FARM_RTOL, atol=FARM_ATOL)
        check(f"farm_{field}", close,
              f"max |diff| {float(np.max(np.abs(a - b))):.3g}, bit-equal "
              f"{bool(np.array_equal(a, b))}, rtol {FARM_RTOL} atol "
              f"{FARM_ATOL}")
    check("farm_epochs", np.array_equal(farm.epochs, one.epochs),
          f"max epochs {int(farm.epochs.max())} vs {int(one.epochs.max())}")
    check("farm_converged", float(farm.violation.max()) <= TOL,
          f"max violation {float(farm.violation.max()):.4g} <= {TOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: Fit A and Fit B; 4: the stage-2 device farm "
                         "against one device")
    args = ap.parse_args(argv)
    try:
        import jax
        from repro.compile_cache import enable_compilation_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import jax and the repro package next to "
              f"this script: {e}", file=sys.stderr)
        return 2
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"this smoke runs on the chip only", file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} needs exactly "
              f"{args.chips} TPU device(s), JAX sees {len(devices)}",
              file=sys.stderr)
        return 1
    cache = enable_compilation_cache()
    print(f"device: {dev.device_kind} x{len(devices)}; compile cache {cache}",
          flush=True)
    check = Checks()
    check_tpu_route(check)
    t0 = time.perf_counter()
    if args.chips == 4:
        run_farm(args.seed, check)
    else:
        run_fits(args.seed, check)
    print(f"total {time.perf_counter() - t0:.3f} s", flush=True)
    if check.failed:
        print(f"chip_smoke: failed checks: {', '.join(check.failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
