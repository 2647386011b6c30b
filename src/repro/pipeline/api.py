"""Config-driven SA→Nyström estimator: KDE → SA leverage → landmark
sampling → streaming Nyström solve → batched predict.

This is the deployment surface of the paper: every stage is Õ(n) time and
O(tile · m) memory, so a single CPU fits n = 10^6 and a mesh shards rows
over the "rows" logical axis (mesh axis "data") with one psum for the
normal equations and one for the KDE grid — activate a mesh with
`repro.distributed.sharding` and the same `fit` call runs sharded, no code
change.

`fit` is a fold over `repro.pipeline.stages` stage objects (see that module
and pipeline/README.md for the stage contract and how to compose custom
workloads — precomputed densities, fixed landmarks, KDE-only benchmarking):

  1. kde       — `stages.DensityStage`: binned FFT KDE for d <= 3 (windowed
                 streaming CIC scatter on XLA, the Pallas `kde_binned`
                 kernel on TPU, `kde_binned_sharded` under a mesh); direct
                 tiled KDE otherwise;
  2. leverage  — `stages.LeverageStage`: Eq. 6 closed form / grid /
                 quadrature, elementwise in the densities;
  3. sample    — `stages.SampleStage`: Gumbel top-k without replacement +
                 importance weights by default; iid with replacement (paper
                 Thm 2) behind `sample_with_replacement=True`;
  4. solve     — `stages.SolveStage` -> `nystrom.fit_streaming`: G =
                 K_nm^T K_nm and rhs = K_nm^T y accumulated over row tiles
                 (lax.scan on XLA, the fused Pallas `gram` kernel on TPU) —
                 the (n, m) cross-kernel matrix is never materialized;
  5. predict   — `stages.PredictStage` -> `nystrom.predict_streaming`,
                 O(tile · m) per batch, row-sharded under a mesh;
  6. score     — `stages.ScoreStage`: mse/rmse against observed targets,
                 the paper's R_n risk against f_star when known.

`predict` runs through the same stage fold as `fit` (so its backend/tile
overrides and wall-clock seconds follow the same contract), and
`evaluate(x, y, f_star=...)` folds all six stages in one `run_stages` pass —
the entry point that measures the paper's §4.1 claim end-to-end.
`calibrate(x, y, ...)` prepends a `stages.CalibrateStage`: a one-fold
(lam, h) grid sweep whose Gram accumulation is shared across the lam grid
and whose KDE deposit is shared across the bandwidth grid (see the stage
docstring and pipeline/README.md "Tuning λ and h"), rewriting lam/bandwidth
for the full-data refit that follows in the same fold.

Each stage records its wall-clock seconds in `state.seconds`, so benchmarks
(benchmarks/bench_pipeline.py, incl. `--stages kde`/`--stages score`
subsets) get the trajectory for free.  The same intervals are program spans
(`repro.spans`): under a profiler session each fit leaves a ``repro/fit``
span holding one ``repro/<stage>`` span per stage, on the device trace's
clock.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Optional, Sequence

import jax

from repro import spans
from repro.core import kernels, leverage, nystrom
from repro.pipeline import stages as stages_mod

Array = jax.Array

# numbers the ``repro/fit`` spans of the process, whichever pipeline runs
# them, so that a trace tells apart the stage spans of consecutive fits
_FITS = itertools.count(1)


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Everything the pipeline needs, serializable via to_dict/from_dict.

    lam / num_landmarks default to the paper's rates when None:
    lam = 0.075 n^{-2/3}, m = 5 n^{1/3} (clipped to >= 8).

    ``SCHEMA_VERSION`` stamps every `to_dict` payload.  `from_dict` refuses
    a dict stamped with a DIFFERENT version (a persisted artifact from an
    incompatible library revision must fail loudly at load, not mis-predict
    silently at serve time); an unstamped dict is accepted as the
    pre-versioning legacy layout.  `repro.serving.ServableKRR` persists
    exactly this dict inside its npz bundle, so the stamp rides through the
    serving save/load round-trip too.
    """

    SCHEMA_VERSION = 2

    # kernel
    kernel_kind: str = "matern"       # "matern" | "gaussian"
    nu: float = 1.5                   # Matern smoothness (0.5 / 1.5 / 2.5)
    lengthscale: float = 1.0          # Matern lengthscale
    sigma: float = 1.0                # Gaussian bandwidth
    # regression
    lam: float | None = None
    num_landmarks: int | None = None
    jitter: float = 1e-6
    # leverage estimation
    leverage_method: str = "closed_form"   # closed_form | grid | quadrature
    kde_method: str = "auto"               # auto | binned | direct
    kde_bandwidth: float | None = None     # fixed h; None -> Scott's rule
    kde_grid_size: int | None = None
    kde_tile: int | None = None            # rows per streaming scatter slab
    density_floor: float | None = None
    # calibration (CalibrateStage / SAKRRPipeline.calibrate): explicit
    # candidate grids, or None for the default factor grids bracketing the
    # paper-rate lam and Scott's-rule h (stages.DEFAULT_LAM_FACTORS/
    # DEFAULT_H_FACTORS)
    lam_grid: tuple[float, ...] | None = None
    h_grid: tuple[float, ...] | None = None
    calibrate_val_fraction: float = 0.2    # holdout share of the one CV fold
    # k-fold selection: 1 keeps the historical single holdout fold
    # bit-for-bit; k > 1 runs the shared-Gram sweep once per fold and
    # averages the per-candidate val MSE (k x the cost, k x lower selection
    # variance — the fold axis rides the same multi-lam machinery)
    calibrate_folds: int = 1
    # sampling
    sample_with_replacement: bool = False  # paper Thm 2 iid mode when True
    # execution
    # rows per streaming slab; None autotunes per (device, op, shape bucket)
    # through repro.tuning (roofline-ranked, cache-persisted — see
    # pipeline/README.md "Autotuning & tile selection")
    tile: int | None = None
    backend: str = "auto"             # auto | xla | pallas (dispatch.resolve)
    # autotune=True additionally MEASURES the top roofline candidates with a
    # one-off cached micro-benchmark during fit/evaluate/calibrate (same
    # numerics either way — tuning only picks tile sizes)
    autotune: bool = False
    # streaming-accumulation strategy (repro.core.streaming): "plain" is the
    # historical fp32 running sum, "compensated" the two-float (Kahan)
    # error-carrying sum — lower Gram noise floor, ~2 extra adds per tile
    accumulator: str = "plain"        # plain | compensated
    # Gram-contraction precision mode (repro.core.precision): "fp32" is the
    # historical dot, "bf16x2"/"bf16x3" split the kernel tiles into bf16
    # words (MXU-rate partial matmuls, error-compensated combine).  None
    # autotunes the (tile, precision) pair jointly when the tile is also
    # None, and means "fp32" when the tile is pinned (bit parity) — see
    # pipeline/README.md "Precision modes".
    precision: str | None = None      # None | fp32 | bf16x2 | bf16x3
    seed: int = 0

    def build_kernel(self) -> kernels.Kernel:
        if self.kernel_kind == "matern":
            return kernels.Matern(nu=self.nu, lengthscale=self.lengthscale)
        if self.kernel_kind == "gaussian":
            return kernels.Gaussian(sigma=self.sigma)
        raise ValueError(f"unknown kernel_kind {self.kernel_kind!r}")

    def resolve_lam(self, n: int) -> float:
        return self.lam if self.lam is not None else 0.075 * n ** (-2.0 / 3.0)

    def resolve_num_landmarks(self, n: int) -> int:
        if self.num_landmarks is not None:
            return self.num_landmarks
        return max(8, int(5 * n ** (1.0 / 3.0)))

    def to_dict(self) -> dict[str, Any]:
        return dict(dataclasses.asdict(self),
                    schema_version=self.SCHEMA_VERSION)

    # tuple-typed fields that JSON round-trips as lists; from_dict restores
    # the tuples so the frozen dataclass stays hashable and == its pre-dump
    # self (the servable-artifact contract: `repro.serving` persists exactly
    # this dict)
    _TUPLE_FIELDS = ("lam_grid", "h_grid")

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "PipelineConfig":
        d = dict(d)
        version = d.pop("schema_version", None)
        if version is not None and version != cls.SCHEMA_VERSION:
            raise ValueError(
                f"PipelineConfig schema_version mismatch: the dict was "
                f"written at version {version!r} but this library reads "
                f"version {cls.SCHEMA_VERSION}; re-export the config (or "
                "the serving artifact carrying it) with a matching library "
                "revision")
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(d) - known)
        if unknown:
            raise ValueError(
                f"unknown PipelineConfig key(s) {unknown}; known fields: "
                f"{sorted(known)} (a config dict from a newer version of "
                "this library cannot be loaded here)")
        for name in cls._TUPLE_FIELDS:
            if d.get(name) is not None:
                d[name] = tuple(float(v) for v in d[name])
        return cls(**d)


@dataclasses.dataclass
class PipelineState:
    """Everything `fit` produced (arrays are O(n) or O(m), never O(n·m)).

    Fields past `num_landmarks` are Optional because a partial stage list
    (e.g. bench --stages kde) legitimately stops before producing them.
    """

    n: int
    d: int
    lam: float
    num_landmarks: int
    densities: Optional[Array]              # (n,)
    leverage: Optional[leverage.SALeverage]
    fit: Optional[nystrom.NystromFit]
    seconds: dict[str, float]               # per-stage wall clock
    sample_weights: Optional[Array] = None  # (m,) inverse-inclusion weights
    predictions: Optional[Array] = None     # (n_eval,) PredictStage output
    scores: Optional[dict[str, float]] = None  # ScoreStage metrics
    bandwidth: Optional[float] = None       # calibrated KDE h (CalibrateStage)
    cv_scores: Optional[list] = None        # per-(lam, h) candidate records
    cv_best: Optional[dict] = None          # winning candidate summary
    batched_fit: Optional[nystrom.BatchedNystromFit] = None  # fit_many


class SAKRRPipeline:
    """sklearn-shaped estimator over the streaming SA→Nyström stack.

    `stages` overrides the default KDE→leverage→sample→solve composition —
    pass any sequence of `repro.pipeline.stages.Stage` objects (e.g. swap in
    `PrecomputedDensityStage` / `FixedLandmarkStage`, or reconfigure a
    single stage's backend/tile) and `fit` folds the context through them.
    """

    def __init__(self, config: PipelineConfig | None = None,
                 stages: Sequence[stages_mod.Stage] | None = None):
        self.config = config or PipelineConfig()
        self.kernel = self.config.build_kernel()
        self.stages = (list(stages) if stages is not None
                       else stages_mod.default_stages(self.config))
        self.state: PipelineState | None = None
        self._ctx: stages_mod.StageContext | None = None
        self._online = None   # pipeline.online.OnlineState, lazy

    # ------------------------------------------------------------------ fit --
    def _make_context(self, x: Array, y: Array,
                      **eval_inputs: Any) -> stages_mod.StageContext:
        cfg = self.config
        n, d = x.shape
        return stages_mod.StageContext(
            config=cfg, kernel=self.kernel, x=x, y=y, n=n, d=d,
            lam=cfg.resolve_lam(n),
            num_landmarks=cfg.resolve_num_landmarks(n), **eval_inputs)

    def _snapshot(self, ctx: stages_mod.StageContext) -> None:
        self._ctx = ctx
        self._online = None   # a fresh fold supersedes any online state
        self.state = PipelineState(
            n=ctx.n, d=ctx.d, lam=ctx.lam, num_landmarks=ctx.num_landmarks,
            densities=ctx.densities, leverage=ctx.leverage, fit=ctx.fit,
            seconds=ctx.seconds, sample_weights=ctx.sample_weights,
            predictions=ctx.predictions, scores=ctx.scores,
            bandwidth=ctx.bandwidth, cv_scores=ctx.cv_scores,
            cv_best=ctx.cv_best, batched_fit=ctx.batched_fit)

    def _run(self, stage_list: Sequence[stages_mod.Stage],
             ctx: stages_mod.StageContext) -> None:
        """`run_stages` under the config's tuning mode: `autotune=True`
        enables measured plan selection (`repro.tuning.measured`) for every
        tile the fold resolves — cached, so only the first cold fold pays."""
        if getattr(self.config, "autotune", False):
            from repro import tuning
            with tuning.measured():
                stages_mod.run_stages(stage_list, ctx)
        else:
            stages_mod.run_stages(stage_list, ctx)

    def fit(self, x: Array, y: Array) -> "SAKRRPipeline":
        with spans.span("repro/fit", fit=next(_FITS)):
            ctx = self._make_context(x, y)
            self._run(self.stages, ctx)
            self._snapshot(ctx)
        return self

    # ------------------------------------------------------------- fit_many --
    def fit_many(self, x: Array, ys: Array, *,
                 lams: Array | Sequence[float] | float | None = None,
                 share_landmarks: bool = False) -> "SAKRRPipeline":
        """Fit MANY tenant models over ONE shared x tile stream.

        `ys` is (B, n) — B target vectors over the same design `x` — and
        `lams` an optional (B,) per-model regularization (scalar / None
        broadcasts; None means the config/paper-rate lam).  The shared
        KDE -> leverage front end runs ONCE; `stages.BatchedSampleStage`
        draws B landmark sets from the one leverage distribution (ONE set
        when ``share_landmarks=True``), and `stages.BatchedSolveStage` ->
        `nystrom.fit_streaming_batched` accumulates all B normal equations
        in one pass over the x tiles — the per-tile cross-kernel block is
        the dominant cost and is paid once per model only in FLOPs, never
        in data movement.  Under a 2D (data x model) mesh the model axis
        shards the B models; see pipeline/README.md "Meshes & many-model
        batching".

        The batched artifact lands on `state.batched_fit`; serve it with
        `predict_many`.
        """
        ys = jax.numpy.asarray(ys)
        if ys.ndim == 1:
            raise ValueError(
                f"fit_many wants ys of shape (num_models, n); got "
                f"{ys.shape} — use fit() for a single model")
        ctx = self._make_context(x, ys[0])
        ctx.ys = ys
        if lams is not None:
            ctx.lams = jax.numpy.broadcast_to(
                jax.numpy.asarray(lams, jax.numpy.float32),
                (int(ys.shape[0]),))
        # reuse the fitted front end (custom density/leverage stages keep
        # their overrides); the single-model sample/solve/... tail is
        # replaced by the batched pair
        prefix = []
        for s in self.stages:
            if getattr(s, "name", "") in ("sample", "solve", "predict",
                                          "score", "calibrate"):
                break
            prefix.append(s)
        if not prefix:
            prefix = [stages_mod.DensityStage(), stages_mod.LeverageStage()]
        solve = self._solve_stage()
        stage_list = prefix + [
            stages_mod.BatchedSampleStage(
                share_landmarks=share_landmarks,
                with_replacement=self.config.sample_with_replacement),
            stages_mod.BatchedSolveStage(
                backend=self._predict_backend(), tile=self._predict_tile(),
                weighted=solve.weighted if solve is not None else False,
                accumulator=solve.accumulator if solve is not None else None,
                precision=self._solve_precision())]
        self._run(stage_list, ctx)
        self._snapshot(ctx)
        return self

    def predict_many(self, x_new: Array, tile: int | None = None) -> Array:
        """(B, n_new) predictions from the `fit_many` artifact — one x_new
        tile stream feeds every model's landmark block (model-axis-sharded
        under a 2D mesh)."""
        st = self._fitted_state()
        if st.batched_fit is None:
            raise RuntimeError("call fit_many(x, ys) before predict_many()")
        with spans.span("repro/predict_many") as sp:
            preds = nystrom.predict_streaming_batched(
                self.kernel, st.batched_fit, jax.numpy.asarray(x_new),
                tile=self._predict_tile(tile),
                backend=self._predict_backend(),
                precision=self._solve_precision())
            jax.block_until_ready(preds)
        st.seconds["predict_many"] = sp.seconds
        return preds

    # ---------------------------------------------------------- partial_fit --
    @property
    def online(self):
        """The live `repro.pipeline.online.OnlineState` (lazy: seeded from
        the banked SolveStage state on first `partial_fit`)."""
        if self._online is None:
            from repro.pipeline import online as online_mod
            if self._ctx is None:
                raise RuntimeError("call fit(x, y) before going online")
            solve = self._solve_stage()
            self._online = online_mod.from_context(
                self._ctx,
                weighted=solve.weighted if solve is not None else False)
        return self._online

    def partial_fit(self, x_new: Array, y_new: Array, *,
                    decay: float | None = None,
                    window: int | None = None) -> "SAKRRPipeline":
        """Absorb new rows and re-solve WITHOUT re-streaming the old data.

        The SolveStage banked its raw normal-equation accumulator state at
        fit time (`repro.core.accstate` — the finalize of the stream it
        already ran, deferred for free), so appending k rows costs
        O(k · m) for the Gram absorb plus ONE O(m^3) solve — independent
        of the rows already absorbed.  On a single-device XLA stream the
        absorb continues the scan carry, so a tile-aligned sequence of
        `partial_fit` calls reproduces the one-shot `fit` beta bit-for-bit
        under the plain accumulator (and within the compensated tolerance
        otherwise).

        ``decay=gamma`` exponentially forgets the past before absorbing
        (drifting streams); ``window=k`` keeps a ring of the last k chunks
        and refolds them (bounded-horizon streams).  The landmark set is
        FROZEN — pair with `repro.pipeline.online.OnlineLandmarks` when
        the dictionary itself must track the drift.

        Updates `state.fit` / the live context in place, so `predict`
        serves the refreshed model immediately.  Returns self.
        """
        st = self._fitted_state()
        if st.fit is None:
            raise RuntimeError("the fitted stage list produced no solve; "
                               "include a SolveStage to partial_fit")
        with spans.span("repro/partial_fit") as sp:
            online = self.online
            online.absorb(self.kernel, jax.numpy.asarray(x_new),
                          jax.numpy.asarray(y_new), decay=decay,
                          window=window)
            fit_ = online.solve_fit(self._ctx.lam, jitter=self.config.jitter)
            jax.block_until_ready(fit_.beta)
            self._ctx.fit = st.fit = fit_
            self._ctx.solve_state = online.solve
        st.seconds["partial_fit"] = sp.seconds
        return self

    # ------------------------------------------------------------- evaluate --
    def evaluate(self, x: Array, y: Array, *, f_star: Array | None = None,
                 x_eval: Array | None = None, y_eval: Array | None = None
                 ) -> dict[str, float]:
        """KDE -> leverage -> sample -> solve -> predict -> score in ONE
        `run_stages` fold.

        Default is the paper's in-sample setting (predict at x, mse against
        y, risk against f_star when the workload knows the noiseless truth);
        pass x_eval/y_eval for held-out scoring.  Returns the ScoreStage
        metrics dict; the full artifacts (predictions, per-stage seconds)
        land on `self.state` like fit's do.  A custom stage list is
        COMPLETED, not truncated: missing Predict/Score stages are appended
        (evaluate always scores — use `fit` for folds that must stop
        earlier).
        """
        ctx = self._make_context(x, y, x_eval=x_eval, y_eval=y_eval,
                                 f_star=f_star)
        eval_stages = self._completed_eval_stages()
        ctx.fuse_scoring = self._can_fuse(eval_stages, x_eval, y_eval)
        self._run(eval_stages, ctx)
        self._snapshot(ctx)
        return dict(ctx.scores or {})

    @staticmethod
    def _can_fuse(stage_list: Sequence[stages_mod.Stage],
                  x_eval: Array | None, y_eval: Array | None) -> bool:
        """Fused in-sample scoring is only valid when every eval input is
        the paper's default (predict at x, score against y/f_star): any
        caller- or stage-level eval override falls back to the explicit
        predict-then-score fold."""
        if x_eval is not None or y_eval is not None:
            return False
        for s in stage_list:
            if getattr(s, "x_eval", None) is not None:
                return False
            if isinstance(s, stages_mod.ScoreStage) and (
                    s.y_eval is not None or s.f_star is not None):
                return False
        return True

    def _completed_eval_stages(self) -> list[stages_mod.Stage]:
        """self.stages COMPLETED to a scoring fold (Predict/Score appended
        when missing — shared by evaluate() and calibrate())."""
        eval_stages = list(self.stages)
        if not any(isinstance(s, stages_mod.PredictStage)
                   for s in eval_stages):
            # insert before any user-supplied ScoreStage (which requires the
            # predictions artifact), else append
            at = next((i for i, s in enumerate(eval_stages)
                       if isinstance(s, stages_mod.ScoreStage)),
                      len(eval_stages))
            eval_stages.insert(at, stages_mod.PredictStage(
                backend=self._predict_backend(), tile=self._predict_tile(),
                precision=self._solve_precision()))
        if not any(isinstance(s, stages_mod.ScoreStage) for s in eval_stages):
            eval_stages.append(stages_mod.ScoreStage())
        return eval_stages

    # ------------------------------------------------------------ calibrate --
    def calibrate(self, x: Array, y: Array, *, f_star: Array | None = None,
                  x_eval: Array | None = None, y_eval: Array | None = None
                  ) -> dict[str, Any]:
        """One-fold (lam, h) sweep, then the full evaluate fold at the winner.

        Prepends a `CalibrateStage` (unless the stage list already has one)
        to the completed evaluate fold: the stage sweeps
        `config.lam_grid` x `config.h_grid` (default factor grids around the
        paper rate / Scott's rule) through ONE shared-expensive-work holdout
        fold — one Gram accumulation per h re-solved per lam, one KDE
        deposit for all h — then rewrites ctx.lam/ctx.bandwidth so the
        stages after it refit the FULL data at the best candidate.

        Returns {"lam", "bandwidth", "val_mse", "cv_scores", "scores"}; the
        fitted artifacts and per-candidate seconds land on `self.state` like
        fit's do (state.cv_scores / state.cv_best / state.lam).
        """
        ctx = self._make_context(x, y, x_eval=x_eval, y_eval=y_eval,
                                 f_star=f_star)
        cal_stages = self._completed_eval_stages()
        ctx.fuse_scoring = self._can_fuse(cal_stages, x_eval, y_eval)
        if not any(isinstance(s, stages_mod.CalibrateStage)
                   for s in cal_stages):
            # mirror ALL of the SolveStage's per-stage overrides (backend,
            # tile, weighted, accumulator, precision) so every candidate is
            # scored under the same solve configuration the winning refit
            # will use
            solve = self._solve_stage()
            cal_stages.insert(0, stages_mod.CalibrateStage(
                backend=self._predict_backend(), tile=self._predict_tile(),
                weighted=solve.weighted if solve is not None else False,
                accumulator=solve.accumulator if solve is not None else None,
                precision=self._solve_precision()))
        self._run(cal_stages, ctx)
        self._snapshot(ctx)
        return dict(ctx.cv_best or {}, cv_scores=ctx.cv_scores,
                    scores=dict(ctx.scores or {}))

    # -------------------------------------------------------------- predict --
    def _solve_stage(self) -> "stages_mod.SolveStage | None":
        return next((s for s in self.stages
                     if isinstance(s, stages_mod.SolveStage)), None)

    def _predict_backend(self) -> str | None:
        # honor the SolveStage's per-stage overrides so fit and predict run
        # the same backend/tile unless the caller says otherwise
        solve = self._solve_stage()
        return (solve.backend if solve is not None and
                solve.backend is not None
                else stages_mod.resolve_backend(self.config))

    def _predict_tile(self, tile: int | None = None) -> int | None:
        """None falls through to autotune inside `nystrom.predict_streaming`."""
        if tile is not None:
            return tile
        solve = self._solve_stage()
        return (solve.tile if solve is not None and solve.tile is not None
                else self.config.tile)

    def _solve_precision(self) -> str | None:
        solve = self._solve_stage()
        return (solve.precision if solve is not None and
                solve.precision is not None
                else getattr(self.config, "precision", None))

    def predict(self, x_new: Array, tile: int | None = None) -> Array:
        st = self._fitted_state()
        if st.fit is None:
            raise RuntimeError("the fitted stage list produced no solve; "
                               "include a SolveStage to predict")
        # predict is the same stage fold as fit — one PredictStage — but it
        # folds over a SHALLOW PER-CALL COPY of the fitted context: the
        # fitted snapshot (state.scores from a prior evaluate(), the
        # evaluate-time predictions, the eval inputs) stays untouched, and
        # interleaved / concurrent predict calls each own their context so
        # they cannot corrupt each other's results.  Only the stage's
        # wall-clock is folded back (state.seconds is additive metadata).
        ctx = dataclasses.replace(
            self._ctx, x_eval=None, y_eval=None, f_star=None,
            predictions=None, scores=None, score_moments=None, seconds={})
        stage = stages_mod.PredictStage(
            x_eval=x_new, backend=self._predict_backend(),
            tile=self._predict_tile(tile), precision=self._solve_precision())
        self._run([stage], ctx)
        self.state.seconds["predict"] = ctx.seconds["predict"]
        return ctx.predictions

    def fitted(self, x_train: Array) -> Array:
        """In-sample predictions (the paper's R_n functional)."""
        return self.predict(x_train)

    # ---------------------------------------------------------------- misc --
    def _fitted_state(self) -> PipelineState:
        if self.state is None:
            raise RuntimeError("call fit(x, y) before predict()")
        return self.state

    @property
    def d_stat(self) -> float:
        lev = self._fitted_state().leverage
        if lev is None:
            raise RuntimeError("the fitted stage list produced no leverage "
                               "scores; include a LeverageStage for d_stat")
        return float(lev.d_stat)

    @property
    def seconds(self) -> dict[str, float]:
        return dict(self._fitted_state().seconds)
