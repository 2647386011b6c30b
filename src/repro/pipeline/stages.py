"""Composable pipeline stages: KDE, leverage, sampling, solve, predict and
score as uniform stage objects.

`SAKRRPipeline.fit` / `.predict` / `.evaluate` are folds over a list of
stages.  Each stage reads and writes named artifacts on a shared
`StageContext` (densities -> leverage -> landmark_idx -> fit ->
predictions -> scores), declares what it `requires`/`provides`, and records
its own wall-clock seconds — so benchmarks get per-stage timing for free
and new workloads compose instead of forking the pipeline class:

  * precomputed densities:  [PrecomputedDensityStage(p), LeverageStage(),
                             SampleStage(), SolveStage()]
  * fixed landmarks:        [FixedLandmarkStage(idx), SolveStage()]
  * KDE-only benchmarking:  [DensityStage()]          (bench --stages kde)
  * end-to-end evaluation:  default_stages() + [PredictStage(),
                             ScoreStage()]            (bench --stages score)

Per-stage execution config (backend / tile / sharding) is a constructor
argument on the stage, overriding the pipeline-wide `PipelineConfig`
defaults; 'auto' resolves from the platform inside `repro.kernels.dispatch`
(Pallas on TPU, XLA elsewhere).

Sharding: stages are mesh-aware through `repro.distributed.sharding`.
Under an active mesh, DensityStage routes the binned KDE through
`core.distributed.kde_binned_sharded` (rows scattered locally, one grid
psum) and SolveStage's normal-equation pass (`nystrom.normal_eq_state`)
shards its row stream; with no mesh both run single-device, same numbers.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core import (accstate, kde, kernels, leverage, nystrom, sampling,
                        streaming)

Array = jax.Array


class StageError(RuntimeError):
    """A stage was run before its required artifacts existed."""


@dataclasses.dataclass
class StageContext:
    """Shared state the stages fold over (arrays are O(n) or O(m))."""

    config: Any                   # PipelineConfig (untyped: avoid the cycle)
    kernel: kernels.Kernel
    x: Array
    y: Array
    n: int
    d: int
    lam: float
    num_landmarks: int
    bandwidth: Optional[float] = None       # KDE h; None -> Scott's rule
    densities: Optional[Array] = None
    leverage: Optional[leverage.SALeverage] = None
    landmark_idx: Optional[Array] = None
    sample_weights: Optional[Array] = None
    fit: Optional[nystrom.NystromFit] = None
    # calibration outputs (CalibrateStage): per-candidate records + the
    # winning (lam, bandwidth) pair (which also rewrites lam/bandwidth above)
    cv_scores: Optional[list] = None
    cv_best: Optional[dict] = None
    # evaluation inputs (PredictStage/ScoreStage): default to in-sample
    x_eval: Optional[Array] = None          # defaults to x
    y_eval: Optional[Array] = None          # observed targets at x_eval
    f_star: Optional[Array] = None          # noiseless truth at x_eval
    predictions: Optional[Array] = None
    scores: Optional[dict[str, float]] = None
    # fused in-sample scoring (SAKRRPipeline.evaluate/calibrate set
    # fuse_scoring): SolveStage banks the score moments (G, K_nm^T t, t^T t)
    # in the SAME row stream that builds the normal equations, PredictStage
    # skips its pass, and ScoreStage assembles mse/risk from the moments —
    # evaluate() then streams x at most twice (deposit + Gram) instead of
    # three times.  Raw `run_stages` folds keep the historical
    # predict-then-score path (fuse_scoring defaults False).
    fuse_scoring: bool = False
    score_moments: Optional[dict] = None
    # first-class accumulator state (repro.core.accstate): SolveStage banks
    # the raw normal-equation fold here for free (same stream, finalize
    # deferred), so `SAKRRPipeline.partial_fit` can absorb new tiles and
    # re-solve in O(tile * m) without ever re-streaming the old rows
    solve_state: Optional[nystrom.NormalEqState] = None
    # many-model batched fits (SAKRRPipeline.fit_many): B tenant models
    # sharing the row stream — per-model responses / regularizers /
    # landmark sets ride a leading model axis that the "models" sharding
    # rule may split across a 2D (data, model) mesh
    ys: Optional[Array] = None              # (B, n) per-model responses
    lams: Optional[Array] = None            # (B,) per-model regularizers
    landmark_sets: Optional[Array] = None   # (B, m) per-model landmark idx
    batch_weights: Optional[Array] = None   # (B, m) importance weights
    batched_fit: Optional["nystrom.BatchedNystromFit"] = None
    seconds: dict[str, float] = dataclasses.field(default_factory=dict)

    def require(self, *names: str) -> None:
        missing = [a for a in names if getattr(self, a) is None]
        if missing:
            raise StageError(
                f"missing artifacts {missing}; run the providing stage(s) "
                "first (e.g. DensityStage before LeverageStage)")


class Stage:
    """Base class: `run(ctx)` produces artifacts; `__call__` times it.

    Subclasses set `name` (the `seconds` key), `requires`/`provides`
    (artifact names on StageContext), and may take per-stage overrides
    (backend, tile, ...) in their constructor.
    """

    name: str = "stage"
    requires: tuple[str, ...] = ()
    provides: tuple[str, ...] = ()

    def run(self, ctx: StageContext) -> None:
        raise NotImplementedError

    def span_stats(self, ctx: StageContext) -> dict:
        """Counters that the stage's span carries (none by default)."""
        del ctx
        return {}

    def __call__(self, ctx: StageContext) -> StageContext:
        ctx.require(*self.requires)
        # the block keeps the stage's device work inside its span, so the
        # span bounds that work on the trace and seconds mean what they say
        with spans.span(f"repro/{self.name}", **self.span_stats(ctx)) as sp:
            self.run(ctx)
            for name in self.provides:
                art = getattr(ctx, name)
                if art is not None:
                    jax.block_until_ready(jax.tree.leaves(art))
        ctx.seconds[self.name] = sp.seconds
        return ctx


def _resolve_kde_method(method: str, d: int) -> str:
    return ("binned" if d <= 3 else "direct") if method == "auto" else method


class DensityStage(Stage):
    """p_hat(x_i) via binned (d <= 3) or direct KDE; mesh-aware.

    Under an active `repro.distributed.sharding` mesh the binned path runs
    `core.distributed.kde_binned_sharded` on grid bounds computed from the
    global data (so it matches the single-device `kde.kde_binned` grid
    exactly); otherwise `kde.estimate_densities`.  `backend`/`tile` override
    the config-wide deposit-stage knobs; `sharded=False` forces the
    single-device path even under a mesh.  On the sharded path the span
    ``repro/kde/bandwidth`` bounds the bandwidth and global bounds, and the
    stage's span carries ``chips`` and ``psum_bytes`` (the lattice state
    each chip all-reduces).
    """

    name = "kde"
    provides = ("densities",)

    def __init__(self, *, method: str | None = None, h: float | None = None,
                 grid_size: int | None = None, backend: str | None = None,
                 tile: int | None = None, sharded: bool | None = None,
                 accumulator: str | None = None):
        self.method = method
        self.h = h
        self.grid_size = grid_size
        self.backend = backend
        self.tile = tile
        self.sharded = sharded
        self.accumulator = accumulator

    def _grid_size(self, ctx: StageContext) -> int:
        return (self.grid_size or ctx.config.kde_grid_size
                or kde.default_grid_size(ctx.d))

    def _sharded(self, ctx: StageContext) -> bool:
        """Whether the sharded binned path runs: under a mesh, binned
        method, and not forced off."""
        from repro.distributed import sharding as shd

        method = _resolve_kde_method(self.method or ctx.config.kde_method,
                                     ctx.d)
        use_sharded = self.sharded if self.sharded is not None else True
        return method == "binned" and use_sharded and shd.active() is not None

    def span_stats(self, ctx: StageContext) -> dict:
        from repro.distributed import sharding as shd

        if not self._sharded(ctx):
            return {}
        chips = shd.active().mesh.devices.size
        if ctx.n % chips:     # the sharded KDE falls back to one device
            return {}
        _, _, accumulator, _ = resolve_exec(self, ctx.config,
                                            tile_attr="kde_tile")
        grid = jax.ShapeDtypeStruct((self._grid_size(ctx),) * ctx.d,
                                    ctx.x.dtype)
        return {"chips": chips,
                "psum_bytes": streaming.state_nbytes(accumulator, grid)}

    def run(self, ctx: StageContext) -> None:
        cfg = ctx.config
        method = _resolve_kde_method(self.method or cfg.kde_method, ctx.d)
        grid_size = self._grid_size(ctx)
        backend, tile, accumulator, _ = resolve_exec(self, cfg,
                                                     tile_attr="kde_tile")
        # bandwidth resolution: stage override > calibrated ctx.bandwidth >
        # config > Scott's rule (the pre-calibration default)
        h = self.h if self.h is not None else ctx.bandwidth
        if h is None:
            h = getattr(cfg, "kde_bandwidth", None)
        if self._sharded(ctx):
            from repro.core import distributed as dist
            with spans.span("repro/kde/bandwidth"):
                h, lo, hi = dist.grid_geometry(ctx.x, h)
            ctx.densities = dist.kde_binned_sharded(
                ctx.x, h, grid_size=grid_size, lo=lo, hi=hi, tile=tile,
                backend=backend, accumulator=accumulator)
        else:
            ctx.densities = kde.estimate_densities(
                ctx.x, h=h, method=method, grid_size=grid_size,
                backend=backend, tile=tile, accumulator=accumulator)


class PrecomputedDensityStage(Stage):
    """Drop-in density source for workloads that already know p(x_i)."""

    name = "kde"
    provides = ("densities",)

    def __init__(self, densities: Array):
        self.densities = densities

    def run(self, ctx: StageContext) -> None:
        if self.densities.shape != (ctx.n,):
            raise ValueError(
                f"precomputed densities have shape {self.densities.shape}, "
                f"expected ({ctx.n},)")
        ctx.densities = jnp.asarray(self.densities)


class LeverageStage(Stage):
    """SA leverage scores (paper Eq. 6) from the densities, elementwise."""

    name = "leverage"
    requires = ("densities",)
    provides = ("leverage",)

    def __init__(self, *, method: str | None = None):
        self.method = method

    def run(self, ctx: StageContext) -> None:
        cfg = ctx.config
        ctx.leverage = leverage.sa_leverage(
            ctx.densities, ctx.lam, ctx.kernel, ctx.d, n=ctx.n,
            method=self.method or cfg.leverage_method,
            floor=cfg.density_floor)


class SampleStage(Stage):
    """m landmarks ~ q: Gumbel top-k without replacement by default
    (distinct landmarks + importance weights), iid with replacement (paper
    Thm 2 setting) behind `config.sample_with_replacement`."""

    name = "sample"
    requires = ("leverage",)
    provides = ("landmark_idx",)

    def __init__(self, *, with_replacement: bool | None = None):
        self.with_replacement = with_replacement

    def run(self, ctx: StageContext) -> None:
        cfg = ctx.config
        key = jax.random.PRNGKey(cfg.seed)
        probs = ctx.leverage.probs
        with_rep = (self.with_replacement if self.with_replacement is not None
                    else cfg.sample_with_replacement)
        m = ctx.num_landmarks
        if with_rep or m > ctx.n:   # top-k needs m distinct points to exist
            ctx.landmark_idx = sampling.sample_with_replacement(key, probs, m)
        else:
            with spans.span("repro/sample/top_k"):
                ctx.landmark_idx, ctx.sample_weights = (
                    sampling.sample_weighted_without_replacement(key, probs,
                                                                 m))


class FixedLandmarkStage(Stage):
    """Drop-in landmark source (precomputed index set; skips KDE/leverage)."""

    name = "sample"
    provides = ("landmark_idx",)

    def __init__(self, landmark_idx: Array):
        self.landmark_idx = landmark_idx

    def run(self, ctx: StageContext) -> None:
        ctx.landmark_idx = jnp.asarray(self.landmark_idx, dtype=jnp.int32)


class SolveStage(Stage):
    """Streaming Nystrom normal equations on the sampled landmarks
    (lax.scan row slabs on XLA, the fused Pallas `gram` kernel on TPU;
    rows psum-sharded under an active mesh).

    ``weighted=True`` feeds the without-replacement importance weights
    (`ctx.sample_weights`, when present) into the column-rescaled SoR solve
    (`nystrom.weighted_normal_eq`).  The SoR predictor is invariant to the
    rescaling in exact arithmetic, so this is off by default — fp32
    whitening order shifts results slightly and the unweighted solve is the
    parity oracle for the dense path.

    ``accumulator`` ("plain" | "compensated", default from the config)
    picks the `repro.core.streaming` Gram-accumulation strategy; the
    compensated two-float sum also lowers the solve's spectral truncation
    floor (`nystrom.solve_normal_eq(eps_scale=...)`).  ``precision``
    ("fp32" | "bf16x2" | "bf16x3" | None, default from the config) picks
    the Gram-contraction mode (`repro.core.precision`).

    Under ``ctx.fuse_scoring`` with in-sample evaluation inputs, the
    score targets ride the rhs of the SAME pass as extra columns
    (`nystrom.normal_eq_state` over [y, f_star]) and the quadratic-form
    moments land on ``ctx.score_moments`` (`score_moments`) —
    PredictStage/ScoreStage then finish the fold without re-streaming x."""

    name = "solve"
    requires = ("landmark_idx",)
    provides = ("fit",)

    def __init__(self, *, backend: str | None = None, tile: int | None = None,
                 weighted: bool = False, accumulator: str | None = None,
                 precision: str | None = None):
        self.backend = backend
        self.tile = tile
        self.weighted = weighted
        self.accumulator = accumulator
        self.precision = precision

    @staticmethod
    def _fuse(ctx: StageContext) -> bool:
        return (ctx.fuse_scoring and ctx.x_eval is None
                and ctx.y_eval is None
                and (ctx.f_star is None or ctx.f_star.shape[0] == ctx.n))

    def span_stats(self, ctx: StageContext) -> dict:
        """Under a mesh that shards the rows: ``chips`` and ``psum_bytes``,
        the normal-equation state (G and the rhs columns) each chip
        all-reduces."""
        chips = streaming.row_shard_count(ctx.x.shape)
        if chips == 1:
            return {}
        _, _, accumulator, _ = resolve_exec(self, ctx.config)
        m = ctx.landmark_idx.shape[0]
        cols = 1 + (self._fuse(ctx) and ctx.f_star is not None)
        dt = jnp.promote_types(ctx.x.dtype, jnp.float32)
        state = (jax.ShapeDtypeStruct((m, m), dt),
                 jax.ShapeDtypeStruct((m, cols), dt))
        return {"chips": chips,
                "psum_bytes": streaming.state_nbytes(accumulator, state)}

    def run(self, ctx: StageContext) -> None:
        cfg = ctx.config
        weights = ctx.sample_weights if self.weighted else None
        backend, tile, accumulator, precision = resolve_exec(self, cfg)
        if not self._fuse(ctx):
            ctx.fit, ctx.solve_state = nystrom.fit_streaming(
                ctx.kernel, ctx.x, ctx.y, ctx.lam, ctx.landmark_idx,
                tile=tile, backend=backend, jitter=cfg.jitter,
                weights=weights, accumulator=accumulator,
                precision=precision, return_state=True)
            return
        targets = [t for t in (ctx.y, ctx.f_star) if t is not None]
        cols = jnp.stack([jnp.asarray(t, ctx.x.dtype) for t in targets],
                         axis=1)                          # (n, 1 + r)
        state = nystrom.normal_eq_state(
            ctx.kernel, ctx.x, cols, ctx.landmark_idx, tile=tile,
            backend=backend, accumulator=accumulator, precision=precision)
        ctx.score_moments = score_moments(state, ctx.y, ctx.f_star)
        ctx.solve_state = nystrom.normal_eq_response(state)
        ctx.fit = nystrom.solve_from_state(ctx.solve_state, ctx.lam,
                                           jitter=cfg.jitter, weights=weights)


def score_moments(state: nystrom.NormalEqState, y: Array,
                  f_star: Array | None) -> dict:
    """The in-sample score moments of a state whose rhs columns are
    K_nm^T [y, f_star]: the *unweighted* G, one K_nm^T t column per target
    and the host-f64 t^T t scalars.

    The in-sample MSE and risk are quadratic forms in what the pass
    already holds,

        sum_i (f(x_i) - t_i)^2 = beta^T G beta - 2 beta^T (K_nm^T t) + t^T t,

    so ScoreStage assembles them in f64 from these instead of a predict
    pass (the two big terms cancel to ~n * mse, so f64 assembly keeps ~7
    significant digits of the score; locked at rtol 2e-3 against the
    predict-pass scores in tests/test_multi_reduce.py).
    """
    g, rhs = accstate.finalize(state.acc)
    y64 = np.asarray(y, np.float64)
    moments = {"g": g, "rhs_y": rhs[:, 0], "n_eval": int(y64.shape[0]),
               "y_sq": float(y64 @ y64), "rhs_f": None, "f_sq": None}
    if f_star is not None:
        f64 = np.asarray(f_star, np.float64)
        moments["rhs_f"] = rhs[:, 1]
        moments["f_sq"] = float(f64 @ f64)
    return moments


class BatchedSampleStage(Stage):
    """Per-model landmark draws for the many-model fold (`fit_many`).

    Every model draws its own landmark set from the SHARED leverage
    distribution (the models share x, so they share densities/leverage);
    the draws are vectorized — one (B, n) Gumbel field, vmapped top-k —
    instead of B python-level sampling calls.  ``share_landmarks=True``
    broadcasts ONE draw to every model (cheaper downstream Grams when
    tenants may share a dictionary); the default keeps per-model draws so
    tenant models stay independently sampled.  Weights follow SampleStage:
    inverse-inclusion importance weights for the without-replacement
    default, none for the with-replacement (paper Thm 2) mode.
    """

    name = "sample"
    requires = ("leverage", "ys")
    provides = ("landmark_sets",)

    def __init__(self, *, share_landmarks: bool = False,
                 with_replacement: bool | None = None):
        self.share_landmarks = share_landmarks
        self.with_replacement = with_replacement

    def run(self, ctx: StageContext) -> None:
        cfg = ctx.config
        probs = ctx.leverage.probs
        big = int(ctx.ys.shape[0])
        m = min(ctx.num_landmarks, ctx.n)
        key = jax.random.PRNGKey(cfg.seed)
        with_rep = (self.with_replacement if self.with_replacement is not None
                    else cfg.sample_with_replacement) or ctx.num_landmarks > ctx.n
        if self.share_landmarks:
            if with_rep:
                idx = sampling.sample_with_replacement(key, probs, m)
                weights = None
            else:
                idx, weights = sampling.sample_weighted_without_replacement(
                    key, probs, m)
            ctx.landmark_sets = jnp.broadcast_to(idx[None, :], (big, m))
            ctx.batch_weights = (None if weights is None else
                                 jnp.broadcast_to(weights[None, :], (big, m)))
            return
        if with_rep:
            keys = jax.random.split(key, big)
            ctx.landmark_sets = jax.vmap(
                lambda k: sampling.sample_with_replacement(k, probs, m))(keys)
            ctx.batch_weights = None
            return
        race_dtype = jnp.promote_types(ctx.x.dtype, jnp.float32)
        races = jax.random.gumbel(key, (big, ctx.n), dtype=race_dtype)
        ctx.landmark_sets, ctx.batch_weights = jax.vmap(
            lambda g: sampling.sample_weighted_without_replacement(
                key, probs, m, gumbel=g))(races)


class BatchedSolveStage(Stage):
    """B independent normal-equation fits off ONE shared row stream
    (`nystrom.fit_streaming_batched`): per-model (y, lam, landmark set),
    rows psummed over the data axis, models sharded over the model axis of
    a 2D mesh.  Execution knobs follow the SolveStage convention (stage
    constructor beats config); ``weighted=True`` applies the per-model
    importance weights banked by BatchedSampleStage."""

    name = "solve"
    requires = ("landmark_sets", "ys")
    provides = ("batched_fit",)

    def __init__(self, *, backend: str | None = None, tile: int | None = None,
                 weighted: bool = False, accumulator: str | None = None,
                 precision: str | None = None):
        self.backend = backend
        self.tile = tile
        self.weighted = weighted
        self.accumulator = accumulator
        self.precision = precision

    def run(self, ctx: StageContext) -> None:
        cfg = ctx.config
        backend, tile, accumulator, precision = resolve_exec(self, cfg)
        lams = ctx.lams if ctx.lams is not None else ctx.lam
        weights = ctx.batch_weights if self.weighted else None
        ctx.batched_fit = nystrom.fit_streaming_batched(
            ctx.kernel, ctx.x, ctx.ys, lams, ctx.landmark_sets,
            tile=tile, backend=backend, jitter=cfg.jitter, weights=weights,
            accumulator=accumulator, precision=precision)


class PredictStage(Stage):
    """Batched predictions at `x_eval` (default: in-sample, ctx.x) through
    `nystrom.predict_streaming` — O(tile * m) per batch, row-sharded under
    an active mesh exactly like the solve.  backend/tile/precision overrides
    follow the SolveStage convention (stage constructor beats config).

    When the fold fused its scoring (SolveStage banked ``ctx.score_moments``
    for the in-sample setting), there is nothing left to predict: the stage
    records its (near-zero) seconds and leaves ``ctx.predictions`` None —
    ScoreStage assembles the metrics from the moments instead.  Explicit
    eval points always run the real pass and invalidate the moments."""

    name = "predict"
    requires = ("fit",)
    provides = ("predictions",)

    def __init__(self, *, x_eval: Array | None = None,
                 backend: str | None = None, tile: int | None = None,
                 precision: str | None = None):
        self.x_eval = x_eval
        self.backend = backend
        self.tile = tile
        self.precision = precision

    def run(self, ctx: StageContext) -> None:
        cfg = ctx.config
        if self.x_eval is not None:
            ctx.x_eval = jnp.asarray(self.x_eval)
        if ctx.x_eval is None:
            ctx.x_eval = ctx.x                       # the paper's R_n setting
            if ctx.score_moments is not None:        # fused in-sample scoring
                return
        ctx.score_moments = None   # real predictions supersede the moments
        backend, tile, _, precision = resolve_exec(self, cfg)
        ctx.predictions = nystrom.predict_streaming(
            ctx.kernel, ctx.fit, ctx.x_eval, tile=tile, backend=backend,
            precision=precision)


class ScoreStage(Stage):
    """Scalar quality metrics from the predictions (or the fused moments).

    Emits a dict on `ctx.scores`:

      * ``mse`` / ``rmse``  — against the observed targets ``y_eval``
        (defaulting to ctx.y when the predictions are in-sample);
      * ``risk``            — the paper's R_n functional, against the
        noiseless ``f_star`` when the workload knows it (synthetic data).

    When the fold fused its scoring (``ctx.score_moments`` from
    SolveStage, no predictions), the metrics come from the quadratic-form
    identity  sum (f - t)^2 = beta^T G beta - 2 beta^T (K_nm^T t) + t^T t
    assembled in host f64 — the two big terms cancel to ~n·mse, so f64
    keeps the score accurate where f32 assembly would lose it.

    Values are host floats (the stage blocks on them, so its recorded
    seconds include the device work it triggered).
    """

    name = "score"
    requires = ()     # predictions OR score_moments; checked in run()
    provides = ("scores",)

    def __init__(self, *, f_star: Array | None = None,
                 y_eval: Array | None = None):
        self.f_star = f_star
        self.y_eval = y_eval

    @staticmethod
    def _scores_from_moments(ctx: StageContext) -> dict[str, float]:
        mom = ctx.score_moments
        beta = np.asarray(ctx.fit.beta, np.float64)
        q = beta @ np.asarray(mom["g"], np.float64) @ beta
        n_eval = mom["n_eval"]
        mse = max(0.0, float(
            (q - 2.0 * (beta @ np.asarray(mom["rhs_y"], np.float64))
             + mom["y_sq"]) / n_eval))
        scores = {"mse": mse, "rmse": mse ** 0.5}
        if mom.get("rhs_f") is not None:
            scores["risk"] = max(0.0, float(
                (q - 2.0 * (beta @ np.asarray(mom["rhs_f"], np.float64))
                 + mom["f_sq"]) / n_eval))
        return scores

    def run(self, ctx: StageContext) -> None:
        if self.y_eval is not None:
            ctx.y_eval = jnp.asarray(self.y_eval)
        if self.f_star is not None:
            ctx.f_star = jnp.asarray(self.f_star)
        if ctx.predictions is None:
            # stage-level targets describe a predict pass the fused fold
            # never ran — they cannot be scored from the moments
            if (ctx.score_moments is not None and self.y_eval is None
                    and self.f_star is None):
                if ctx.y_eval is None and ctx.x_eval is ctx.x:
                    ctx.y_eval = ctx.y               # in-sample default
                ctx.scores = self._scores_from_moments(ctx)
                return
            raise StageError(
                "missing artifacts ['predictions']; run the providing "
                "stage(s) first (e.g. PredictStage before ScoreStage)")
        if ctx.y_eval is None and ctx.x_eval is ctx.x:
            ctx.y_eval = ctx.y                       # in-sample default
        if ctx.y_eval is None and ctx.f_star is None:
            raise StageError(
                "ScoreStage needs targets: set y_eval and/or f_star (on the "
                "stage or the context) for out-of-sample predictions")
        pred = ctx.predictions
        scores: dict[str, float] = {}
        if ctx.y_eval is not None:
            mse = float(jnp.mean((pred - ctx.y_eval) ** 2))
            scores["mse"] = mse
            scores["rmse"] = mse ** 0.5
        if ctx.f_star is not None:
            scores["risk"] = float(jnp.mean((pred - ctx.f_star) ** 2))
        ctx.scores = scores


# ----------------------------------------------------------- calibration --

# Default grids when neither the stage nor the config pins them: lam
# candidates bracket the paper's asymptotic rate symmetrically in log space,
# bandwidth candidates bracket Scott's rule.  Both contain the 1.0 factor,
# so the paper-rate default is always IN the swept set — calibration can
# only match or beat it on the validation fold.
DEFAULT_LAM_FACTORS = (0.1, 0.3, 1.0, 3.0, 10.0)
DEFAULT_H_FACTORS = (0.5, 1.0, 2.0)


class CalibrateStage(Stage):
    """One-fold (lam, h) cross-validation with SHARED expensive work.

    The sweep's costs factor exactly:

      * the tiled Gram ``K_nm^T K_nm`` / moments are lam-independent, so each
        bandwidth candidate accumulates them ONCE and re-solves the
        whitened normal equations per lam (`nystrom.fit_streaming` over the
        lam grid: one `normal_eq_state` pass, one `solve_from_state` —
        bit-equal to per-lam `fit_streaming` loops, at 1/L of the
        row-stream cost);
      * the binned-KDE CIC deposit is h-independent on a fixed grid, so the
        whole bandwidth grid shares ONE deposit and only the FFT smooth +
        gather re-run per h (`kde.kde_binned_multi`;
        `core.distributed.kde_binned_sharded_multi` under an active mesh —
        one deposit AND one grid psum for the whole sweep);
      * validation scoring shares ONE x_val stream across the WHOLE
        (h, lam) grid (`nystrom.val_mse_streaming_multi` — a fused
        `streaming.multi_reduce` scan with one squared-error accumulator
        slot per bandwidth), recorded as ``seconds["calibrate[val]"]``.

    So an H x L sweep costs ~H fits + one KDE instead of H·L of each.  The
    fold: a deterministic holdout split (``val_fraction``, seeded by the
    config; the train side is rounded to divide an active mesh so the Gram
    psum stays sharded), per-h densities -> SA leverage at the reference
    ctx.lam -> one landmark draw per h sharing ONE Gumbel race (the noise
    is drawn once and passed to every draw via ``gumbel=``, so the h axis
    of the sweep differs only through the probs — zero sampling noise
    between candidates, ROADMAP gap (e)) -> multi-lam fit -> multi-lam
    validation MSE.  Emits `ctx.cv_scores` (one record per (h, lam) with
    val_mse/val_rmse and the per-h fit/block seconds), `ctx.cv_best`, and
    REWRITES ``ctx.lam`` / ``ctx.bandwidth`` so every downstream stage
    (DensityStage reads ctx.bandwidth, Leverage/SolveStage read ctx.lam)
    refits the full data at the winning candidate.  Per-h wall-clock lands
    in ``ctx.seconds["calibrate[h=...]"]`` next to the stage total.
    """

    name = "calibrate"
    provides = ("cv_scores",)

    def __init__(self, *, lam_grid: Sequence[float] | None = None,
                 h_grid: Sequence[float] | None = None,
                 val_fraction: float | None = None,
                 folds: int | None = None,
                 backend: str | None = None, tile: int | None = None,
                 weighted: bool = False, accumulator: str | None = None,
                 precision: str | None = None):
        self.lam_grid = lam_grid
        self.h_grid = h_grid
        self.val_fraction = val_fraction
        self.folds = folds
        self.backend = backend
        self.tile = tile
        self.weighted = weighted
        self.accumulator = accumulator
        self.precision = precision

    # ------------------------------------------------------------ helpers --
    def _grids(self, ctx: StageContext) -> tuple[list[float], list[float]]:
        cfg = ctx.config
        lam_grid = self.lam_grid or getattr(cfg, "lam_grid", None)
        if lam_grid is None:
            lam_grid = [f * ctx.lam for f in DEFAULT_LAM_FACTORS]
        h_grid = self.h_grid or getattr(cfg, "h_grid", None)
        if h_grid is None:
            # bracket the user-pinned bandwidth when one is configured (so
            # the configured candidate is always IN the swept set and can
            # only be beaten, never silently discarded), else Scott's rule
            h0 = getattr(cfg, "kde_bandwidth", None)
            h0 = float(h0) if h0 is not None else float(
                kde.scott_bandwidth(ctx.x))
            h_grid = [f * h0 for f in DEFAULT_H_FACTORS]
        return [float(l) for l in lam_grid], [float(h) for h in h_grid]

    def _split(self, ctx: StageContext) -> tuple[Array, Array]:
        """Deterministic holdout (train_idx, val_idx); the train side is
        shrunk (val grows) until it divides an active mesh, so the shared
        Gram/deposit run sharded with their single psum."""
        from repro.distributed import sharding as shd
        cfg = ctx.config
        frac = (self.val_fraction if self.val_fraction is not None
                else getattr(cfg, "calibrate_val_fraction", 0.2))
        n_val = min(ctx.n - 1, max(1, int(frac * ctx.n)))
        act = shd.active()
        if act is not None:
            size = act.mesh.devices.size
            n_tr = ctx.n - n_val
            if n_tr > size:    # else: leave it; the kernels fall back local
                n_val += n_tr % size
        perm = jax.random.permutation(jax.random.PRNGKey(cfg.seed ^ 0x5EED),
                                      ctx.n)
        return perm[n_val:], perm[:n_val]

    def _folds(self, ctx: StageContext) -> list[tuple[Array, Array]]:
        """The fold list: k == 1 (the default) reproduces the historical
        holdout split bit-for-bit; k > 1 slices ONE deterministic
        permutation into k equal validation blocks (remainder rows stay on
        every fold's train side), each train side shrunk to divide an
        active mesh exactly like `_split`.  k-fold selection runs the
        shared-Gram sweep k times — k× the cost for k× lower selection
        variance, the fold axis riding the same multi-lam machinery."""
        from repro.distributed import sharding as shd
        cfg = ctx.config
        k = (self.folds if self.folds is not None
             else getattr(cfg, "calibrate_folds", 1))
        k = int(k)
        if k < 1:
            raise ValueError(f"calibrate folds must be >= 1, got {k}")
        if k == 1:
            return [self._split(ctx)]
        if k > ctx.n:
            raise ValueError(f"cannot make {k} folds from {ctx.n} rows")
        perm = jax.random.permutation(jax.random.PRNGKey(cfg.seed ^ 0x5EED),
                                      ctx.n)
        fs = ctx.n // k
        act = shd.active()
        folds: list[tuple[Array, Array]] = []
        for j in range(k):
            val = perm[j * fs:(j + 1) * fs]
            tr = jnp.concatenate([perm[:j * fs], perm[(j + 1) * fs:]])
            if act is not None:
                size = act.mesh.devices.size
                n_tr = int(tr.shape[0])
                if n_tr > size and n_tr % size:
                    extra = n_tr % size
                    val = jnp.concatenate([val, tr[:extra]])
                    tr = tr[extra:]
            folds.append((tr, val))
        return folds

    def _densities_multi(self, ctx: StageContext, x_tr: Array,
                         h_grid: list[float]) -> Array:
        """(H, n_tr) densities at every bandwidth, one deposit (+ one psum
        under a mesh); direct KDE (d > 3) has no shareable deposit and just
        loops."""
        from repro.distributed import sharding as shd
        cfg = ctx.config
        method = _resolve_kde_method(cfg.kde_method, ctx.d)
        if method != "binned":
            return jnp.stack([kde.kde_direct(x_tr, x_tr, h) for h in h_grid])
        grid_size = cfg.kde_grid_size or kde.default_grid_size(ctx.d)
        backend, tile, accumulator, _ = resolve_exec(self, cfg,
                                                     tile_attr="kde_tile",
                                                     stage_tile=False)
        h_max = jnp.asarray(max(h_grid), x_tr.dtype)
        lo, hi = kde.binned_bounds(x_tr, x_tr, h_max)
        if shd.active() is not None:
            from repro.core import distributed as dist
            return dist.kde_binned_sharded_multi(
                x_tr, h_grid, grid_size=grid_size, lo=lo, hi=hi, tile=tile,
                backend=backend, accumulator=accumulator)
        return kde.kde_binned_multi(x_tr, x_tr, h_grid, grid_size,
                                    lo=lo, hi=hi, backend=backend, tile=tile,
                                    accumulator=accumulator)

    # ---------------------------------------------------------------- run --
    def _run_fold(self, ctx: StageContext, lam_grid: list[float],
                  h_grid: list[float], tr_idx: Array, val_idx: Array,
                  tag: str, fold: int) -> tuple[np.ndarray, list[float],
                                                list[float]]:
        """One fold of the (h, lam) sweep: shared deposit, per-h Gram
        re-solved per lam, one fused validation stream.  Returns the fold's
        (H, L) val-MSE matrix and per-h fit/block seconds; per-h wall-clock
        lands in ctx.seconds keyed with `tag` ("" for the single-fold
        sweep — the historical keys — "fJ|" under k-fold)."""
        cfg = ctx.config
        x_tr, y_tr = ctx.x[tr_idx], ctx.y[tr_idx]
        x_val, y_val = ctx.x[val_idx], ctx.y[val_idx]
        n_tr = int(x_tr.shape[0])
        backend, tile, accumulator, precision = resolve_exec(self, cfg)

        with spans.span("repro/calibrate/kde") as kde_span:
            dens = self._densities_multi(ctx, x_tr, h_grid)
            jax.block_until_ready(dens)

        key = jax.random.PRNGKey(cfg.seed)
        if tag:   # k-fold: each fold draws its own race/landmarks
            key = jax.random.fold_in(key, fold)
        # ONE Gumbel race for the whole bandwidth grid: every h's landmark
        # draw perturbs its own probs with the SAME noise, so the h axis of
        # the sweep carries zero sampling noise (drawn once here instead of
        # re-derived from the key inside every per-h call)
        race_dtype = jnp.promote_types(ctx.x.dtype, jnp.float32)
        race = jax.random.gumbel(key, (n_tr,), dtype=race_dtype)
        fits_by_h: list[nystrom.NystromFit] = []
        fit_seconds: list[float] = []
        h_seconds: list[float] = []
        for i, h in enumerate(h_grid):
            with spans.span("repro/calibrate/h", h=h, fold=fold) as h_span:
                lev = leverage.sa_leverage(
                    dens[i], ctx.lam, ctx.kernel, ctx.d, n=n_tr,
                    method=cfg.leverage_method, floor=cfg.density_floor)
                # top-k needs m DISTINCT train points to exist, so the
                # fallback tests the unclamped request (SampleStage
                # semantics) while the draw itself is clamped to the fold
                m = min(ctx.num_landmarks, n_tr)
                if cfg.sample_with_replacement or ctx.num_landmarks > n_tr:
                    idx = sampling.sample_with_replacement(key, lev.probs, m)
                    weights = None
                else:
                    idx, weights = (
                        sampling.sample_weighted_without_replacement(
                            key, lev.probs, m, gumbel=race))
                with spans.span("repro/calibrate/solve") as fit_span:
                    grid = nystrom.fit_streaming(
                        ctx.kernel, x_tr, y_tr, lam_grid, idx,
                        tile=tile, backend=backend, jitter=cfg.jitter,
                        weights=weights if self.weighted else None,
                        accumulator=accumulator, precision=precision)
                    jax.block_until_ready(grid.beta)
            sec_key = f"calibrate[{tag}h={h:.3g}]"
            if sec_key in ctx.seconds:   # grid values equal at 3 sig figs
                sec_key = f"calibrate[{tag}h={h:.3g}#{i}]"
            ctx.seconds[sec_key] = h_span.seconds
            fits_by_h.append(grid)
            fit_seconds.append(fit_span.seconds)
            h_seconds.append(h_span.seconds)
        # validation: ONE fused x_val stream scores every (h, lam) candidate
        # (`nystrom.val_mse_streaming_multi` — slot h applies its own
        # landmarks/betas per tile) instead of H predict passes
        with spans.span("repro/calibrate/val") as val_span:
            val_mse_hl = nystrom.val_mse_streaming_multi(
                [ctx.kernel] * len(h_grid), fits_by_h, x_val, y_val,
                tile=tile, backend=backend, precision=precision)
            val_mse_hl = np.asarray(jax.block_until_ready(val_mse_hl))
        ctx.seconds[f"calibrate[{tag}val]"] = val_span.seconds
        ctx.seconds[f"calibrate[{tag}kde]"] = kde_span.seconds
        return val_mse_hl, fit_seconds, h_seconds

    def run(self, ctx: StageContext) -> None:
        lam_grid, h_grid = self._grids(ctx)
        folds = self._folds(ctx)
        k = len(folds)
        total = np.zeros((len(h_grid), len(lam_grid)))
        fit_seconds = np.zeros(len(h_grid))
        h_seconds = np.zeros(len(h_grid))
        for j, (tr_idx, val_idx) in enumerate(folds):
            tag = "" if k == 1 else f"f{j}|"
            mse_hl, fit_s, h_s = self._run_fold(ctx, lam_grid, h_grid,
                                                tr_idx, val_idx, tag, j)
            total += mse_hl
            fit_seconds += np.asarray(fit_s)
            h_seconds += np.asarray(h_s)
        val_mse_hl = total / k
        records: list[dict] = []
        for i, h in enumerate(h_grid):
            for j, lam in enumerate(lam_grid):
                mse = float(val_mse_hl[i, j])
                records.append({
                    "h": float(h), "lam": float(lam), "val_mse": mse,
                    "val_rmse": mse ** 0.5,
                    "fit_seconds": round(float(fit_seconds[i]), 4),
                    "h_block_seconds": round(float(h_seconds[i]), 4),
                    "best": False})

        # non-finite val_mse (a diverged candidate) must never win min():
        # NaN compares False against everything, so key on finiteness first
        best = min(records, key=lambda r: (not math.isfinite(r["val_mse"]),
                                           r["val_mse"]))
        best["best"] = True
        ctx.cv_scores = records
        ctx.cv_best = {"lam": best["lam"], "bandwidth": best["h"],
                       "val_mse": best["val_mse"], "folds": k}
        # rewrite the downstream knobs: the full-data refit (DensityStage
        # onward) now runs at the calibrated candidate
        ctx.lam = best["lam"]
        ctx.bandwidth = best["h"]
        ctx.densities = ctx.leverage = ctx.landmark_idx = None
        ctx.sample_weights = ctx.fit = ctx.predictions = ctx.scores = None
        ctx.score_moments = ctx.solve_state = None
        ctx.landmark_sets = ctx.batch_weights = ctx.batched_fit = None


def default_stages(config: Any = None) -> list[Stage]:
    """The paper's Algorithm 1 as a stage list: KDE -> leverage -> sample ->
    solve.  Per-stage overrides come from constructing the stages yourself."""
    del config  # stages read the config from the context at run time
    return [DensityStage(), LeverageStage(), SampleStage(), SolveStage()]


def evaluate_stages(config: Any = None) -> list[Stage]:
    """default_stages + in-sample predict/score — one fold from raw data to
    risk numbers (`SAKRRPipeline.evaluate`, bench --stages score)."""
    return default_stages(config) + [PredictStage(), ScoreStage()]


def run_stages(stages: Sequence[Stage], ctx: StageContext,
               until: str | None = None) -> StageContext:
    """Fold ctx through stages; stop (inclusive) at stage name `until`."""
    for stage in stages:
        stage(ctx)
        if until is not None and stage.name == until:
            break
    return ctx


def resolve_backend(cfg: Any) -> str | None:
    """Config backend -> dispatch arg (None lets dispatch resolve 'auto')."""
    return None if cfg.backend == "auto" else cfg.backend


def resolve_accumulator(cfg: Any) -> str:
    """Config accumulation strategy (repro.core.streaming); older configs
    without the field mean the historical plain running sum."""
    return getattr(cfg, "accumulator", None) or "plain"


def resolve_exec(stage: Any, cfg: Any, *, tile_attr: str = "tile",
                 stage_tile: bool = True
                 ) -> tuple[str | None, int | None, str, str | None]:
    """Per-stage execution knobs: (backend, tile, accumulator, precision).

    One resolver for every stage's precedence chain — stage constructor
    override beats the pipeline-wide config default.  ``tile_attr`` names
    the config field the stage's tile falls back to ("tile" for the
    solve/predict row slabs, "kde_tile" for the deposit);
    ``stage_tile=False`` ignores the stage's own tile attribute
    (CalibrateStage's shared deposit reads the config's kde_tile, not the
    stage's Gram tile).  A resolved tile of None means autotune
    (`repro.tuning` through `kernels.dispatch.resolve_plan`); a resolved
    precision of None means the Gram stream picks its (tile, precision)
    pair jointly from the autotune plan — or the historical "fp32" when
    the tile is pinned (`nystrom._gram_exec`).  Stages without a
    Gram contraction (KDE deposit) ignore the precision slot.
    """
    backend = getattr(stage, "backend", None)
    if backend is None:
        backend = resolve_backend(cfg)
    tile = getattr(stage, "tile", None) if stage_tile else None
    if tile is None:
        tile = getattr(cfg, tile_attr, None)
    accumulator = (getattr(stage, "accumulator", None)
                   or resolve_accumulator(cfg))
    precision = getattr(stage, "precision", None)
    if precision is None:
        precision = getattr(cfg, "precision", None)
    return backend, tile, accumulator, precision
