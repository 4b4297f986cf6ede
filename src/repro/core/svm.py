"""Public LPD-SVM estimator: the paper's two-stage algorithm behind one API.

    svm = LPDSVM(kernel=KernelParams("rbf", gamma=2**-7), C=2**5, budget=1000)
    svm.fit(x, y)           # stage 1 (factor G) + stage 2 (dual CA, OVO)
    svm.predict(x_test)

Stage 1 can be reused across fits (cross-validation, C grids, OVO pairs) by
passing a precomputed `LowRankFactor` — see `core/cv.py` which exploits
exactly the reuse pattern the paper measures in Table 3.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dual_solver import SolveResult, SolverConfig, TaskBatch, solve_batch
from repro.core.kernel_fn import KernelParams, gram
from repro.core.nystrom import LowRankFactor, compute_factor, wait_for_factor
from repro.core.ovo import build_ovo_tasks, ovo_decision_values, ovo_vote
from repro.core.polish import (PolishSchedule, PolishTrace, make_schedule,
                               solve_polished)
from repro.core.solver_stream import (Stage2StreamStats, route_stage2,
                                      solve_streamed_auto)
from repro.core.streaming import StreamConfig
from repro.core.trace import resolve as resolve_tracer


@dataclasses.dataclass
class FitStats:
    """Timings of the stages (paper figure 3 breakdown)."""

    stage1_seconds: float = 0.0     # preparation + computation of G
    stage2_seconds: float = 0.0     # linear SVM training (SMO)
    n_tasks: int = 0
    epochs: Optional[np.ndarray] = None
    violations: Optional[np.ndarray] = None
    effective_rank: int = 0
    stage1_streamed: bool = False   # True -> G came from the out-of-core path
    stage1_stats: Optional[object] = None  # streaming.Stage1StreamStats
                                           # (chunk wire bytes / dtype)
    stage2_streamed: bool = False   # True -> solver streamed G row-blocks
    stage2_stats: Optional[Stage2StreamStats] = None
    polished: bool = False          # True -> stage 2 ran the polish ladder
    polish_trace: Optional[PolishTrace] = None  # per-level epochs/violations/
                                                # duality-gap trajectory


class LPDSVM:
    def __init__(
        self,
        kernel: KernelParams = KernelParams("rbf", gamma=1.0),
        C: float = 1.0,
        budget: int = 1000,
        tol: float = 1e-2,
        max_epochs: int = 1000,
        shrink: bool = True,
        seed: int = 0,
        gram_fn: Callable = gram,
        solve_fn: Callable = solve_batch,
        stream: Optional[bool] = None,
        stream_config: Optional[StreamConfig] = None,
        polish: bool = False,
        polish_levels: int = 3,
        polish_schedule: Optional[PolishSchedule] = None,
        polish_gap_trace: bool = True,
    ):
        self.kernel = kernel
        self.C = float(C)
        self.budget = int(budget)
        self.config = SolverConfig(tol=tol, max_epochs=max_epochs, shrink=shrink)
        self.seed = seed
        self.gram_fn = gram_fn
        self.solve_fn = solve_fn
        # Out-of-core training: `stream` forces it, `stream_config`'s device
        # budget auto-routes it (see core/streaming.py + core/solver_stream.py
        # — both stages stream, so fitting scales past HBM end to end); both
        # None -> always the monolithic device-resident paths.
        self.stream = stream
        self.stream_config = stream_config
        # Polishing (core/polish.py): coarse-to-fine warm-started stage 2.
        # `polish=True` builds the default geometric ladder (`polish_levels`
        # deep); an explicit `polish_schedule` wins.
        self.polish_schedule = (
            polish_schedule if polish_schedule is not None
            else make_schedule(levels=polish_levels) if polish else None)
        # Per-level duality gaps in the trace cost extra host/device work at
        # scale (one G sweep per task per level) — disablable for hot fits.
        self.polish_gap_trace = polish_gap_trace
        # fitted state
        self.factor: Optional[LowRankFactor] = None
        self.classes_: Optional[np.ndarray] = None
        self.pairs_ = None
        self.W_: Optional[jnp.ndarray] = None      # (T, B) per-pair weights
        self.alpha_: Optional[jnp.ndarray] = None  # (T, n_pad)
        self.tasks_: Optional[TaskBatch] = None
        self.stats = FitStats()

    # ------------------------------------------------------------------ stage 1
    def prepare(self, x: np.ndarray, trace=None) -> LowRankFactor:
        """Compute (or return the cached) low-rank factor G for `x`."""
        if self.factor is None:
            tr = resolve_tracer(
                trace if trace is not None
                else getattr(self.stream_config, "trace", None))
            t0 = tr.begin("fit", "stage1")
            if self.stream or self.stream_config is not None:
                # Host numpy in, so the streamed path never materialises the
                # full x on device; the monolithic path converts internally.
                x = np.asarray(x, np.float32)
            self.factor = compute_factor(
                x, self.kernel, self.budget,
                key=jax.random.PRNGKey(self.seed), gram_fn=self.gram_fn,
                stream=self.stream, stream_config=self.stream_config)
            wait_for_factor(self.factor.G)
            self.stats.stage1_seconds = tr.end(
                t0, rows=int(np.asarray(x).shape[0]), budget=self.budget)
            self.stats.effective_rank = self.factor.effective_rank
            self.stats.stage1_streamed = self.factor.streamed
            self.stats.stage1_stats = getattr(self.factor, "stage1_stats",
                                              None)
        return self.factor

    # ------------------------------------------------------------------ stage 2
    def fit(self, x: np.ndarray, y: np.ndarray,
            factor: Optional[LowRankFactor] = None,
            warm_alpha: Optional[np.ndarray] = None,
            trace=None,
            checkpoint_dir: Optional[str] = None,
            checkpoint_every: Optional[int] = None,
            resume: Optional[bool] = None) -> "LPDSVM":
        """Two-stage fit.  ``trace`` optionally records the run's pipeline
        timeline (a `core.trace.Tracer`): it is threaded into the streamed
        paths via `StreamConfig.trace`, wins over an installed process-wide
        tracer, and with ``trace=None`` the no-op fast path keeps outputs
        bit-identical to an un-instrumented fit.

        ``checkpoint_dir`` / ``checkpoint_every`` / ``resume`` thread
        fault-tolerance into the streamed paths (core/resilience.py): stage 1
        resumes completed G row-chunks from ``<dir>/stage1_G.npy`` and stage 2
        snapshots full solver state every ``checkpoint_every`` full passes,
        resumable bit-exactly after a kill.  Setting any of them forces the
        streamed route (checkpoints only exist there); they are folded into
        ``stream_config`` exactly like ``trace``."""
        if (checkpoint_dir is not None or checkpoint_every is not None
                or resume is not None):
            upd = {}
            if checkpoint_dir is not None:
                upd["checkpoint_dir"] = checkpoint_dir
            if checkpoint_every is not None:
                upd["checkpoint_every"] = int(checkpoint_every)
            if resume is not None:
                upd["resume"] = bool(resume)
            self.stream_config = dataclasses.replace(
                self.stream_config or StreamConfig(), **upd)
            if self.stream is None and self.stream_config.checkpoint_dir:
                self.stream = True   # checkpoints only exist on that path
        if trace is not None and self.stream_config is not None \
                and self.stream_config.trace is None:
            self.stream_config = dataclasses.replace(self.stream_config,
                                                     trace=trace)
        tr = resolve_tracer(
            trace if trace is not None
            else getattr(self.stream_config, "trace", None))
        y = np.asarray(y)
        self.classes_, labels = np.unique(y, return_inverse=True)
        n_classes = len(self.classes_)
        if n_classes < 2:
            raise ValueError("need at least two classes")
        if factor is not None:
            self.factor = factor
            self.stats.effective_rank = factor.effective_rank
            self.stats.stage1_streamed = factor.streamed
            self.stats.stage1_stats = getattr(factor, "stage1_stats", None)
        self.prepare(x, trace=trace)

        warm = None
        if warm_alpha is not None:
            warm = [np.asarray(a) for a in warm_alpha]
        with tr.span("stage2", "build_tasks"):
            tasks, self.pairs_ = build_ovo_tasks(labels, n_classes, self.C,
                                                 alpha0=warm)
        self.tasks_ = tasks
        t0 = tr.begin("fit", "stage2")
        res: SolveResult = self._solve_stage2(tasks, trace=trace)
        wait_for_factor(res.w)
        self.stats.stage2_seconds = tr.end(t0, tasks=tasks.n_tasks)
        self.stats.n_tasks = tasks.n_tasks
        self.stats.epochs = np.asarray(res.epochs)
        self.stats.violations = np.asarray(res.violation)
        self.W_ = res.w
        self.alpha_ = res.alpha
        return self

    def _solve_stage2(self, tasks: TaskBatch, trace=None) -> SolveResult:
        """Stage-2 dispatch (see `solver_stream.route_stage2`): the polish
        ladder when enabled, the streamed row-block solver when G must stay
        host-resident (overlapped over every local device when there are
        several — `solve_streamed_auto`), else the jit'd `solve_batch`."""
        G = self.factor.G
        # Routing always uses self.stream_config (a trace must never change
        # which solver runs); a fit(trace=...) with no explicit StreamConfig
        # still reaches the streamed paths via a default config carrying it.
        cfg = self.stream_config
        if trace is not None and cfg is None:
            cfg = StreamConfig(trace=trace)
        self.stats.stage2_streamed = False      # refits must not report the
        self.stats.stage2_stats = None          # previous fit's stream stats
        self.stats.polished = False
        self.stats.polish_trace = None
        if self.polish_schedule is not None:
            res, ptrace = solve_polished(
                self.factor, tasks, self.config, self.polish_schedule,
                stream=self.stream, stream_config=self.stream_config,
                solve_fn=self.solve_fn, gap_trace=self.polish_gap_trace,
                return_trace=True, trace=trace)
            self.stats.polished = True
            self.stats.polish_trace = ptrace
            self.stats.stage2_streamed = ptrace.final.streamed
            self.stats.stage2_stats = ptrace.final.stream_stats
            return res
        if not route_stage2(self.factor, tasks, self.stream,
                            self.stream_config, self.solve_fn, solve_batch):
            return self.solve_fn(G, tasks, self.config)
        res, stats = solve_streamed_auto(
            G, tasks, self.config, stream_config=cfg, return_stats=True)
        self.stats.stage2_streamed = True
        self.stats.stage2_stats = stats
        return res

    # --------------------------------------------------------------- prediction
    def _tracer(self):
        return resolve_tracer(getattr(self.stream_config, "trace", None))

    def decision_function(self, x: np.ndarray) -> np.ndarray:
        if self.W_ is None:
            raise RuntimeError("fit first")
        tr = self._tracer()
        with tr.span("predict", "features"):
            feats = self.factor.features(jnp.asarray(x, jnp.float32))
        with tr.span("predict", "decide"):
            d = ovo_decision_values(feats, self.W_)
        with tr.span("d2h", "decisions"):
            return np.asarray(d)

    def predict(self, x: np.ndarray) -> np.ndarray:
        d = self.decision_function(x)
        with self._tracer().span("predict", "vote"):
            return self._vote(d)

    def _vote(self, d: np.ndarray) -> np.ndarray:
        if len(self.classes_) == 2:
            pred = np.where(d[:, 0] > 0, 0, 1)
        else:
            pred = ovo_vote(d, self.pairs_, len(self.classes_))
        return self.classes_[pred]

    def predict_from_factor(self, rows: Optional[np.ndarray] = None) -> np.ndarray:
        """Predict TRAINING rows straight from the fitted factor's G — no
        kernel evaluations and no dense x required (the `--libsvm` CLI path
        scores this way so the dense (n, p) matrix is never materialised)."""
        if self.W_ is None:
            raise RuntimeError("fit first")
        G = self.factor.G
        if G.shape[0] == 0:
            raise RuntimeError(
                "G is not persisted in checkpoints (it is recomputable from "
                "the landmarks); refit or use predict(x) on a loaded model")
        g = G if rows is None else G[np.asarray(rows)]
        return self._vote(np.asarray(g @ np.asarray(self.W_).T))

    def score(self, x: np.ndarray, y: np.ndarray) -> float:
        return float(np.mean(self.predict(x) == np.asarray(y)))

    def error(self, x: np.ndarray, y: np.ndarray) -> float:
        return 1.0 - self.score(x, y)

    # -------------------------------------------------------------- persistence
    def save(self, directory: str, step: int = 0) -> str:
        """Persist the fitted model (landmarks + projector + per-pair weights).

        Only stage-1 artifacts and the solution are stored — G itself is a
        training-time object and is NOT persisted (it is n x B; the paper's
        point is that it can always be recomputed from the landmarks).
        ``step`` versions successive saves; `load` picks the latest.
        """
        if self.W_ is None:
            raise RuntimeError("fit first")
        from repro.checkpoint import save_checkpoint
        tree = {
            "landmarks": self.factor.landmarks,
            "projector": self.factor.projector,
            "eigvals": self.factor.eigvals,
            "W": self.W_,
            "classes": jnp.asarray(self.classes_),
            "meta": {
                "gamma": jnp.float32(self.kernel.gamma),
                "coef0": jnp.float32(self.kernel.coef0),
                "degree": jnp.int32(self.kernel.degree),
                "C": jnp.float32(self.C),
                "kind": jnp.int32(("rbf", "linear", "poly", "tanh")
                                  .index(self.kernel.kind)),
            },
        }
        return save_checkpoint(directory, step, tree)

    @classmethod
    def load(cls, directory: str, step: Optional[int] = None) -> "LPDSVM":
        import msgpack  # noqa: F401  (checkpoint backend)
        import os
        from repro.checkpoint import latest_step
        # Discover the newest checkpoint unless a step is pinned; shapes are
        # read straight from the payload (no template needed).
        if step is None:
            step = latest_step(directory)
            if step is None:
                raise FileNotFoundError(f"no step_*.msgpack under {directory}")
        path = os.path.join(directory, f"step_{step:08d}.msgpack")
        with open(path, "rb") as f:
            payload = msgpack.unpackb(f.read(), raw=False)

        def arr(key):
            rec = payload[key]
            return jnp.asarray(np.frombuffer(rec["data"],
                                             dtype=np.dtype(rec["dtype"]))
                               .reshape(rec["shape"]))

        kinds = ("rbf", "linear", "poly", "tanh")
        kernel = KernelParams(
            kind=kinds[int(arr("meta/kind"))],
            gamma=float(arr("meta/gamma")),
            coef0=float(arr("meta/coef0")),
            degree=int(arr("meta/degree")),
        )
        svm = cls(kernel=kernel, C=float(arr("meta/C")))
        landmarks = arr("landmarks")
        projector = arr("projector")
        from repro.core.nystrom import LowRankFactor
        svm.factor = LowRankFactor(
            G=jnp.zeros((0, projector.shape[1]), jnp.float32),
            landmarks=landmarks, projector=projector,
            eigvals=arr("eigvals"),
            effective_rank=projector.shape[1], kernel=kernel)
        svm.W_ = arr("W")
        svm.classes_ = np.asarray(arr("classes"))
        from repro.core.ovo import class_pairs
        svm.pairs_ = class_pairs(len(svm.classes_))
        return svm
