"""End-to-end paper driver: backbone features -> LPD-SVM classifier head.

This is the paper's ImageNet experiment in miniature: a (reduced) assigned
architecture plays VGG-16, its pooled hidden states are the feature vectors,
and LPD-SVM trains the one-vs-one large-margin classifier on top.

    PYTHONPATH=src python -m repro.launch.train_svm --arch qwen3-0.6b \
        --classes 10 --n 4000 --budget 256
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compilation_cache
from repro.configs import get_config
from repro.core import KernelParams, LPDSVM, median_gamma
from repro.models import init_model
from repro.models import model as M


def extract_features(cfg, params, tokens: np.ndarray, batch: int = 32):
    """Mean-pooled final hidden states as feature vectors."""
    outs = []

    @jax.jit
    def embed(toks):
        # forward up to final norm; logits path skipped via tiny trick:
        # reuse forward but take pre-unembed activations by computing
        # logits @ nothing — instead rerun the trunk here.
        x = params["embed"][toks]
        positions = jnp.arange(x.shape[1])
        from repro.models.model import _layout
        from repro.models import blocks
        pro, g, n_groups = _layout(cfg)
        for i, lp in enumerate(params["prologue"]):
            x, _ = blocks.apply_layer_full(lp, cfg, i, x, positions)

        def body(c, gp):
            x = c
            for j in range(g):
                x, _ = blocks.apply_layer_full(gp[j], cfg, pro + j, x, positions)
            return x, None

        if n_groups:
            x, _ = jax.lax.scan(body, x, params["groups"])
        from repro.models.common import rms_norm
        x = rms_norm(x, params["final_ln"], cfg.norm_eps)
        return jnp.mean(x.astype(jnp.float32), axis=1)

    for s in range(0, tokens.shape[0], batch):
        outs.append(np.asarray(embed(jnp.asarray(tokens[s:s + batch]))))
    return np.concatenate(outs, axis=0)


def class_conditioned_tokens(n: int, n_classes: int, seq: int, vocab: int,
                             seed: int = 0, mix: float = 0.5):
    """Synthetic 'documents' whose token statistics depend on the class."""
    rng = np.random.default_rng(seed)
    y = rng.integers(0, n_classes, size=n)
    # each class owns a band of preferred tokens
    band = vocab // (n_classes + 1)
    toks = rng.integers(0, vocab, size=(n, seq))
    for c in range(n_classes):
        mask = rng.random((n, seq)) < mix
        mask &= (y == c)[:, None]
        toks = np.where(mask, rng.integers(c * band, (c + 1) * band,
                                           size=(n, seq)), toks)
    return toks.astype(np.int32), y


def train_from_libsvm(args, stream_config):
    """Out-of-core end-to-end path: LIBSVM file -> CSR -> streamed stage 1
    (`compute_factor_streamed_csr`) -> streamed stage 2.  The dense (n, p)
    matrix is never materialised; training rows are scored from G.

    With ``--shard-dir`` the text is parsed ONCE into the checksummed shard
    store (`core/shards.py`) and this — and every later — run streams the
    verified binary shards instead (`compute_factor_streamed_shards`): a
    reused store performs zero text parses."""
    from repro.core import KernelParams, LPDSVM, StreamConfig
    from repro.core.streaming import (compute_factor_streamed_csr,
                                      compute_factor_streamed_shards)

    cfg = stream_config or StreamConfig()
    kp_gamma = args.gamma
    t0 = time.time()
    if args.shard_dir:
        import os
        from repro.core.shards import ShardStoreStats, open_or_ingest
        sstats = ShardStoreStats()
        store, ingested = open_or_ingest(
            args.libsvm, os.path.join(args.shard_dir, "data"),
            n_features=args.n_features or None,
            shard_rows=cfg.shard_rows,
            dtype="int8" if args.stage1_dtype == "int8" else "f32",
            on_bad_row=args.on_bad_row, verify=cfg.verify_shards,
            retries=0 if cfg.fail_fast else cfg.max_retries,
            retry_backoff=cfg.retry_backoff, stats=sstats, trace=cfg.trace)
        t_read = time.time() - t0
        n, p = store.n, store.cols
        labels = store.labels()
        skipped = int(store.manifest.get("rows_skipped", 0))
        if skipped:
            print(f"libsvm: skipped {skipped} bad row(s) (--on-bad-row skip)")
        if kp_gamma is None:
            rows = np.random.default_rng(0).choice(n, min(256, n),
                                                   replace=False)
            kp_gamma = median_gamma(store.gather_rows(np.sort(rows)))
        kp = KernelParams("rbf", gamma=kp_gamma)
        t0 = time.time()
        factor = compute_factor_streamed_shards(
            store, kp, args.budget, key=jax.random.PRNGKey(0), config=cfg)
        src = "ingested (parsed once)" if ingested else "reused (no parse)"
        shard_line = (f"shards: {store.n_shards} x {store.shard_rows} rows "
                      f"({store.dtype}) under {args.shard_dir} — {src}")
    else:
        from repro.data import IngestStats, read_libsvm
        ingest = IngestStats()
        data = read_libsvm(args.libsvm, n_features=args.n_features or None,
                           on_bad_row=args.on_bad_row, stats=ingest)
        t_read = time.time() - t0
        n, p = data.n, data.n_features
        labels = data.labels
        if ingest.rows_skipped:
            print(f"libsvm: skipped {ingest.rows_skipped} bad row(s) "
                  f"(--on-bad-row skip)")
        if kp_gamma is None:
            # densify only a row subsample for the heuristic (median_gamma's
            # own sampler never sees the CSR rows it was not handed)
            rows = np.random.default_rng(0).choice(n, min(256, n),
                                                   replace=False)
            kp_gamma = median_gamma(data.densify_rows(np.sort(rows)))
        kp = KernelParams("rbf", gamma=kp_gamma)
        t0 = time.time()
        factor = compute_factor_streamed_csr(data, kp, args.budget,
                                             key=jax.random.PRNGKey(0),
                                             config=cfg)
        shard_line = None
    args.gamma = kp_gamma
    t_factor = time.time() - t0
    svm = LPDSVM(kp, C=args.C, budget=args.budget, tol=1e-2,
                 stream=True, stream_config=stream_config,
                 polish=args.polish, polish_levels=args.polish_levels)
    svm.fit(None, labels, factor=factor)
    svm.stats.stage1_seconds = t_factor   # factor was computed out here
    err = float(np.mean(svm.predict_from_factor() != labels))
    print(f"libsvm: {n} rows x {p} features in {t_read:.1f}s")
    if shard_line:
        print(shard_line)
        st = sstats
        line = (f"shard io: {st.shards_read} reads "
                f"{st.bytes_read / 2**20:.1f} MiB "
                f"({st.read_gbps:.2f} GB/s), {st.verifications} verified")
        if st.checksum_failures:
            line += (f", {st.checksum_failures} corrupt -> "
                     f"{st.quarantined} quarantined / {st.rebuilt} rebuilt")
        if st.retries:
            line += f", {st.retries} retried"
        print(line)
    _report(svm)
    print(f"train error: {err:.4f}")
    return err


def _report(svm):
    s1 = svm.stats.stage1_stats
    s2 = svm.stats.stage2_stats
    print(f"stage1 {svm.stats.stage1_seconds:.2f}s (rank "
          f"{svm.stats.effective_rank}"
          f"{', streamed' if svm.stats.stage1_streamed else ''})  "
          f"stage2 {svm.stats.stage2_seconds:.2f}s "
          f"({svm.stats.n_tasks} binary SVMs"
          f"{', streamed' if svm.stats.stage2_streamed else ''})")
    if s1 is not None:
        scales = (f" ({s1.bytes_scales / 2**10:.1f} KiB scales)"
                  if s1.bytes_scales else "")
        print(f"stage1 stream: {s1.chunks} x {s1.wire_dtype} chunks, "
              f"prefetch {s1.prefetch_final}, "
              f"{s1.bytes_h2d / 2**20:.1f} MiB H2D{scales}")
    if s2 is not None:
        print(f"stage2 stream: tile {s2.tile_rows} rows x {s2.block_dtype} "
              f"blocks, {s2.n_devices} device(s), prefetch "
              f"{s2.prefetch_final}, {s2.epochs} epochs, "
              f"{s2.bytes_h2d / 2**20:.1f} MiB H2D"
              + (f" ({s2.bytes_scales / 2**10:.1f} KiB scales)"
                 if s2.bytes_scales else "")
              + f" / {s2.bytes_d2h / 2**20:.1f} MiB D2H, "
              f"active {s2.active_history}")
        # bytes_miss accrues even with the cache off (the cross-run
        # identity needs it); only report when the cache actually ran
        if s2.bytes_hit or s2.cache_resident_bytes:
            total = s2.bytes_hit + s2.bytes_miss
            print(f"stage2 cache: {s2.bytes_hit / 2**20:.1f} MiB hit / "
                  f"{s2.bytes_miss / 2**20:.1f} MiB miss "
                  f"({100 * s2.bytes_hit / total:.0f}% of compacted G bytes "
                  f"served from HBM), peak resident "
                  f"{s2.cache_resident_bytes / 2**20:.1f} MiB, "
                  f"{s2.cache_evictions} evictions")
    tr = svm.stats.polish_trace
    if tr is not None:
        for lv in tr.levels:
            finite = np.isfinite(lv.duality_gap)
            gap = float(np.max(lv.duality_gap[finite])) if finite.any() \
                else float("nan")
            print(f"polish level {lv.fraction:.4g}: {lv.n_rows} rows, "
                  f"tol {lv.tol:.3g}, {int(lv.epochs.max())} epochs max, "
                  f"gap {gap:.3g}, {lv.row_visits} row-visits"
                  f"{', streamed' if lv.streamed else ''}")
        print(f"polish total: {tr.total_row_visits} row-visits over "
              f"{len(tr.levels)} levels")


def _report_grid(res, gammas, Cs):
    """Per-grid summary for --grid-*: selection, errors, and — when the grid
    task farm ran — the one-stream stats each gamma's whole (C x folds) grid
    trained under."""
    print(f"grid: {len(gammas)} gammas x {len(Cs)} Cs, "
          f"{res.n_binary_solved} binary SVMs, "
          f"stage1 {res.stage1_seconds:.2f}s stage2 {res.stage2_seconds:.2f}s")
    for gi, gamma in enumerate(gammas):
        errs = " ".join(f"{e:.4f}" for e in res.errors[gi])
        line = f"  gamma {gamma:.4g}: err [{errs}]"
        if res.stream_stats is not None and res.stream_stats[gi] is not None:
            st = res.stream_stats[gi]
            line += (f"  farm: {st.epochs} epochs, "
                     f"{st.bytes_h2d / 2**20:.1f} MiB H2D "
                     f"({st.bytes_g / 2**20:.1f} MiB G blocks), "
                     f"{st.bytes_d2h / 2**20:.1f} MiB D2H, "
                     f"tile {st.tile_rows} x {st.block_dtype}")
        print(line)
    print(f"grid best: gamma={res.best_gamma:.4g} C={res.best_C:.4g} "
          f"err={res.best_error:.4f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--budget", type=int, default=256)
    ap.add_argument("--C", type=float, default=8.0)
    ap.add_argument("--gamma", type=float, default=None)
    ap.add_argument("--device-budget-mb", type=float, default=0.0,
                    help="device working-set budget for BOTH stages; >0 "
                         "auto-routes onto the out-of-core pipelines when "
                         "the monolithic working set exceeds it")
    ap.add_argument("--chunk-rows", type=int, default=0,
                    help="fixed stage-1 streaming chunk size (0 = derive from "
                         "budget; without --device-budget-mb this forces "
                         "streaming)")
    ap.add_argument("--tile-rows", type=int, default=0,
                    help="fixed stage-2 G block rows (0 = derive from budget)")
    ap.add_argument("--stream", action="store_true",
                    help="force the out-of-core pipelines (both stages) "
                         "regardless of budget")
    ap.add_argument("--block-dtype", choices=("f32", "bf16", "int8"),
                    default="f32",
                    help="wire dtype of streamed stage-2 G blocks; bf16 "
                         "halves the H2D bytes (upcast on device), int8 "
                         "quarters them (per-row-group scale/zero codec, "
                         "fused device dequant); like --tile-rows, a non-f32 "
                         "dtype forces streaming without a budget")
    ap.add_argument("--stage1-dtype", choices=("f32", "int8"), default="f32",
                    help="wire dtype of streamed stage-1 x chunks; int8 "
                         "quarters the chunk H2D bytes with dequantisation "
                         "fused into the gram kernel (forces streaming "
                         "without a budget)")
    ap.add_argument("--quant-group-rows", type=int, default=0,
                    help="rows per int8 scale group (0 = default 32; both "
                         "stages)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="disable the overlapped multi-device stage-2 task "
                         "farm (serial per-device streams; single-device "
                         "hosts are unaffected)")
    ap.add_argument("--cache-budget-mb", type=float, default=-1.0,
                    help="HBM allowance for the stage-2 hot-row block cache "
                         "per device (<0 = the unused remainder of the "
                         "device budget, the default; 0 disables caching)")
    ap.add_argument("--no-cache", action="store_true",
                    help="disable the stage-2 HBM block cache (every "
                         "compacted cheap epoch re-ships the active-row "
                         "union over H2D)")
    ap.add_argument("--polish", action="store_true",
                    help="coarse-to-fine warm-started stage 2: solve a "
                         "nested subsample ladder (n/16 -> n/4 -> n by "
                         "default) with tolerance annealing so the full-data "
                         "pass is a short polish (core/polish.py)")
    ap.add_argument("--polish-levels", type=int, default=3,
                    help="depth of the polish ladder (default 3)")
    ap.add_argument("--grid-cs", default=None,
                    help="comma-separated C grid (e.g. '1,4,16'); with "
                         "--grid-gammas runs the k-fold CV grid search "
                         "instead of a single fit — when the cells stream, "
                         "each gamma's whole (C x folds) grid trains as ONE "
                         "task farm over a single G stream")
    ap.add_argument("--grid-gammas", default=None,
                    help="comma-separated gamma grid for --grid-cs "
                         "(default: the median heuristic's single gamma)")
    ap.add_argument("--grid-folds", type=int, default=3,
                    help="CV folds for the grid search (default 3)")
    ap.add_argument("--libsvm", default=None,
                    help="train from a LIBSVM-format file instead of backbone "
                         "features (end-to-end out-of-core path)")
    ap.add_argument("--n-features", type=int, default=0,
                    help="feature count for --libsvm (0 = infer from file)")
    ap.add_argument("--on-bad-row", choices=("raise", "skip"),
                    default="raise",
                    help="--libsvm ingest policy for malformed / non-finite "
                         "rows: 'raise' (default) aborts naming the line, "
                         "'skip' drops them and reports the count")
    ap.add_argument("--shard-dir", default=None, metavar="DIR",
                    help="durable disk tier (core/shards.py): with --libsvm, "
                         "parse the text ONCE into checksummed binary shards "
                         "under DIR/data and stream every run from them "
                         "(re-runs skip the parse entirely); also the home "
                         "of --spill-g stores; forces the streamed pipelines")
    ap.add_argument("--shard-rows", type=int, default=4096,
                    help="rows per shard file (default 4096; multiple of the "
                         "int8 group size so stored scale groups stay "
                         "global-row-aligned)")
    ap.add_argument("--spill-g", action="store_true",
                    help="stream the stage-1 factor G into f32 shards under "
                         "--shard-dir and run stage 2 straight off the disk "
                         "tier (the (n, B') host buffer never materialises)")
    ap.add_argument("--verify-shards", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="recompute each shard's checksum on every read "
                         "(default on; corrupt shards are quarantined and "
                         "rebuilt from source — --no-verify-shards trusts "
                         "the bytes)")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="fault-tolerance state directory (core/resilience.py)"
                         ": stage 1 streams G into a resumable memmap there, "
                         "stage 2 snapshots full solver state at epoch "
                         "boundaries; forces the streamed pipelines")
    ap.add_argument("--checkpoint-every", type=int, default=1,
                    help="snapshot stage 2 every N full passes (default 1; "
                         "needs --checkpoint-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest snapshot in "
                         "--checkpoint-dir; bit-equal to the uninterrupted "
                         "run when killed at an epoch boundary")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record the run's pipeline timeline (core/trace.py) "
                         "and export it as Chrome-trace JSON loadable in "
                         "Perfetto / chrome://tracing")
    ap.add_argument("--trace-summary", action="store_true",
                    help="print the aggregated trace summary (seconds per "
                         "category, effective H2D GB/s, rows/s, overlap "
                         "efficiency) after the run; implies tracing")
    ap.add_argument("--verbose", action="store_true",
                    help="print one progress line per stage-2 epoch (active "
                         "rows, bytes, cache hit rate, rows/s, max KKT "
                         "violation); implies tracing")
    args = ap.parse_args()
    if args.chunk_rows < 0:
        ap.error(f"--chunk-rows must be >= 0, got {args.chunk_rows}")
    if args.tile_rows < 0:
        ap.error(f"--tile-rows must be >= 0, got {args.tile_rows}")
    if args.polish_levels < 1:
        ap.error(f"--polish-levels must be >= 1, got {args.polish_levels}")
    if args.grid_folds < 2:
        ap.error(f"--grid-folds must be >= 2, got {args.grid_folds}")
    if args.grid_gammas is not None and args.grid_cs is None:
        ap.error("--grid-gammas requires --grid-cs")
    if args.checkpoint_every < 0:
        ap.error(f"--checkpoint-every must be >= 0, got {args.checkpoint_every}")
    if args.resume and not args.checkpoint_dir:
        ap.error("--resume requires --checkpoint-dir")
    if args.shard_rows < 1:
        ap.error(f"--shard-rows must be >= 1, got {args.shard_rows}")
    if args.spill_g and not args.shard_dir:
        ap.error("--spill-g requires --shard-dir")
    enable_compilation_cache()

    stream_config = None
    # An explicit chunk/tile size or wire dtype with no budget is a request
    # to stream, not a hint to the (roomy) default budget; --stream forces.
    from repro.core.quant import GROUP_ROWS
    if args.quant_group_rows < 0:
        ap.error(f"--quant-group-rows must be >= 0, got {args.quant_group_rows}")
    quant = args.block_dtype != "f32" or args.stage1_dtype != "f32"
    # Checkpoints only exist on the streamed paths, so --checkpoint-dir is a
    # request to stream (like an explicit chunk/tile size with no budget).
    force = args.stream or bool(args.checkpoint_dir) or bool(args.shard_dir) \
        or ((args.chunk_rows > 0 or args.tile_rows > 0
             or quant) and args.device_budget_mb <= 0)
    cache_off = args.no_cache or args.cache_budget_mb == 0
    if (args.device_budget_mb > 0 or args.chunk_rows > 0
            or args.tile_rows > 0 or args.stream or quant or args.no_overlap
            or cache_off or args.cache_budget_mb > 0 or args.checkpoint_dir
            or args.shard_dir):
        from repro.core import StreamConfig
        stream_config = StreamConfig(
            device_budget_bytes=int(args.device_budget_mb * 2**20) or 2 << 30,
            chunk_rows=args.chunk_rows or None,
            tile_rows=args.tile_rows or None,
            block_dtype=args.block_dtype,
            stage1_dtype=args.stage1_dtype,
            quant_group_rows=args.quant_group_rows or GROUP_ROWS,
            overlap_devices=not args.no_overlap,
            cache_blocks=not cache_off,
            cache_budget_bytes=(int(args.cache_budget_mb * 2**20)
                                if args.cache_budget_mb > 0 else None),
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=(args.checkpoint_every
                              if args.checkpoint_dir else 0),
            resume=args.resume,
            shard_dir=args.shard_dir,
            shard_rows=args.shard_rows,
            spill_g=args.spill_g,
            verify_shards=args.verify_shards)
        if args.checkpoint_dir:
            print(f"checkpoint: {args.checkpoint_dir} (every "
                  f"{args.checkpoint_every} full passes"
                  f"{', resuming' if args.resume else ''})")

    # Observability (core/trace.py): any of the three flags arms a tracer.
    # It is installed process-wide — every instrumented hot path resolves it
    # even when no StreamConfig exists — and additionally threaded through
    # `StreamConfig.trace` when one does.  Export/summary run in `finally`
    # so a failed run still leaves a timeline to look at.
    tracer = None
    if args.trace or args.trace_summary or args.verbose:
        from repro.core.trace import ProgressPrinter, Tracer, install
        tracer = Tracer()
        if args.verbose:
            tracer.add_listener(ProgressPrinter())
        if stream_config is not None:
            stream_config = dataclasses.replace(stream_config, trace=tracer)
        install(tracer)
    try:
        return _run(args, ap, stream_config, force)
    finally:
        if tracer is not None:
            from repro.core.trace import uninstall
            uninstall()
            if args.trace:
                tracer.export(args.trace)
                print(f"trace: {tracer.n_events} events -> {args.trace}")
            if args.trace_summary:
                print(tracer.summary())


def _run(args, ap, stream_config, force):
    if args.libsvm:
        if args.grid_cs is not None:
            ap.error("--grid-cs is not supported with --libsvm")
        return train_from_libsvm(args, stream_config)

    cfg = get_config(args.arch, reduced=True)
    params, _ = init_model(jax.random.PRNGKey(0), cfg)

    t0 = time.time()
    toks, y = class_conditioned_tokens(args.n, args.classes, args.seq,
                                       cfg.vocab_size)
    feats = extract_features(cfg, params, toks)
    t_feat = time.time() - t0
    if args.gamma is None:
        args.gamma = median_gamma(feats)
    n_tr = int(args.n * 0.8)

    if args.grid_cs is not None:
        from repro.core import grid_search
        Cs = [float(v) for v in args.grid_cs.split(",")]
        gammas = ([float(v) for v in args.grid_gammas.split(",")]
                  if args.grid_gammas else [args.gamma])
        t0 = time.time()
        res = grid_search(feats[:n_tr], y[:n_tr], gammas, Cs,
                          budget=args.budget, folds=args.grid_folds,
                          stream=True if force else None,
                          stream_config=stream_config, polish=args.polish,
                          polish_levels=args.polish_levels)
        print(f"features: {feats.shape} in {t_feat:.1f}s; "
              f"grid search {time.time() - t0:.1f}s")
        _report_grid(res, gammas, Cs)
        svm = LPDSVM(KernelParams("rbf", gamma=res.best_gamma), C=res.best_C,
                     budget=args.budget, tol=1e-2,
                     stream=True if force else None,
                     stream_config=stream_config)
        svm.fit(feats[:n_tr], y[:n_tr])
        err = svm.error(feats[n_tr:], y[n_tr:])
        print(f"test error: {err:.4f} (chance {1 - 1/args.classes:.2f})")
        return err

    svm = LPDSVM(KernelParams("rbf", gamma=args.gamma), C=args.C,
                 budget=args.budget, tol=1e-2,
                 stream=True if force else None,
                 stream_config=stream_config,
                 polish=args.polish, polish_levels=args.polish_levels)
    svm.fit(feats[:n_tr], y[:n_tr])
    err = svm.error(feats[n_tr:], y[n_tr:])
    print(f"features: {feats.shape} in {t_feat:.1f}s")
    _report(svm)
    print(f"test error: {err:.4f} (chance {1 - 1/args.classes:.2f})")
    return err


if __name__ == "__main__":
    main()
