"""Stage layer: composition, artifact dependencies, drop-in stages, the
default-sampler switch (Gumbel top-k without replacement), and the
predict/score fold (stages past fit)."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import krr, nystrom
from repro.data import krr_data
from repro.pipeline import (DensityStage, FixedLandmarkStage, LeverageStage,
                            PipelineConfig, PrecomputedDensityStage,
                            PredictStage, SAKRRPipeline, SampleStage,
                            ScoreStage, SolveStage, StageContext, StageError,
                            default_stages, evaluate_stages, run_stages)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_forced_devices(body: str, devices: int = 2) -> str:
    """Run a snippet in a subprocess with forced host devices (fast enough
    for tier-1: tiny n, single jit each)."""
    code = ("import jax\nimport jax.numpy as jnp\nimport numpy as np\n"
            + textwrap.dedent(body))
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def _ctx(n=1024, d=3, m=32, seed=0):
    data = krr_data.bimodal(jax.random.PRNGKey(seed), n, d=d)
    cfg = PipelineConfig(num_landmarks=m, tile=256)
    return data, StageContext(config=cfg, kernel=cfg.build_kernel(),
                              x=data.x, y=data.y, n=n, d=d,
                              lam=cfg.resolve_lam(n), num_landmarks=m)


def test_default_stage_list_shape_and_seconds():
    stages = default_stages(None)
    assert [s.name for s in stages] == ["kde", "leverage", "sample", "solve"]
    _, ctx = _ctx()
    run_stages(stages, ctx)
    assert set(ctx.seconds) == {"kde", "leverage", "sample", "solve"}
    assert all(v >= 0.0 for v in ctx.seconds.values())
    assert ctx.fit is not None and ctx.fit.beta.shape == (32,)


def test_stage_requires_enforced():
    _, ctx = _ctx()
    with pytest.raises(StageError):
        LeverageStage()(ctx)            # no densities yet
    with pytest.raises(StageError):
        SolveStage()(ctx)               # no landmarks yet


def test_run_stages_until_stops_inclusive():
    _, ctx = _ctx()
    run_stages(default_stages(None), ctx, until="leverage")
    assert ctx.leverage is not None and ctx.landmark_idx is None
    assert set(ctx.seconds) == {"kde", "leverage"}


def test_precomputed_density_stage_drops_in():
    """A pipeline fed the exact densities must match one that runs its own
    KDE stage on those densities' values downstream (same leverage)."""
    data, ctx = _ctx(seed=1)
    run_stages([DensityStage()], ctx)
    dens = ctx.densities
    _, ctx2 = _ctx(seed=1)
    run_stages([PrecomputedDensityStage(dens), LeverageStage()], ctx2)
    _, ctx3 = _ctx(seed=1)
    run_stages([DensityStage(), LeverageStage()], ctx3)
    np.testing.assert_allclose(np.asarray(ctx2.leverage.probs),
                               np.asarray(ctx3.leverage.probs), rtol=1e-6)
    with pytest.raises(ValueError):
        run_stages([PrecomputedDensityStage(dens[:10])], _ctx(seed=1)[1])


def test_fixed_landmark_stage_skips_density_pipeline():
    data, ctx = _ctx(seed=2)
    idx = jnp.arange(0, 1024, 32)[:32]
    run_stages([FixedLandmarkStage(idx), SolveStage()], ctx)
    assert ctx.densities is None            # KDE never ran
    dense = nystrom.fit_from_landmarks(ctx.kernel, data.x, data.y, ctx.lam,
                                       idx)
    want = np.asarray(nystrom.predict(ctx.kernel, dense, data.x[:200]))
    got = np.asarray(nystrom.predict_streaming(ctx.kernel, ctx.fit,
                                               data.x[:200], tile=256))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-3)


def test_pipeline_accepts_custom_stage_list():
    data = krr_data.bimodal(jax.random.PRNGKey(3), 1024, d=3)
    cfg = PipelineConfig(num_landmarks=32, tile=256)
    idx = jnp.arange(32, dtype=jnp.int32) * 7
    pipe = SAKRRPipeline(cfg, stages=[FixedLandmarkStage(idx), SolveStage()])
    pipe.fit(data.x, data.y)
    assert set(pipe.seconds) == {"sample", "solve"}
    risk = float(krr.in_sample_risk(pipe.fitted(data.x), data.f_star))
    assert np.isfinite(risk)
    with pytest.raises(RuntimeError):       # no leverage stage ran
        pipe.d_stat


def test_partial_pipeline_cannot_predict():
    data = krr_data.bimodal(jax.random.PRNGKey(4), 512, d=3)
    pipe = SAKRRPipeline(PipelineConfig(num_landmarks=16, tile=128),
                         stages=[DensityStage()])
    pipe.fit(data.x, data.y)
    assert pipe.state.densities is not None and pipe.state.fit is None
    with pytest.raises(RuntimeError):
        pipe.predict(data.x[:10])


def test_default_sampling_is_without_replacement():
    """Gumbel top-k landmarks are distinct and carry inverse-inclusion
    importance weights; the paper's iid mode stays behind the config flag."""
    data = krr_data.bimodal(jax.random.PRNGKey(5), 4096, d=3)
    cfg = PipelineConfig(num_landmarks=256, tile=1024)
    pipe = SAKRRPipeline(cfg).fit(data.x, data.y)
    idx = np.asarray(pipe.state.fit.landmark_idx)
    assert len(np.unique(idx)) == 256       # distinct by construction
    w = np.asarray(pipe.state.sample_weights)
    assert w.shape == (256,)
    # inverse inclusion probabilities: always >= 1, and not all saturated
    # at the certain-inclusion value (the SA probs are far from uniform)
    assert np.all(w >= 1.0)
    assert np.max(w) > 1.5

    wr = PipelineConfig(num_landmarks=256, tile=1024,
                        sample_with_replacement=True)
    pipe_wr = SAKRRPipeline(wr).fit(data.x, data.y)
    assert pipe_wr.state.sample_weights is None
    # with replacement at m=256 on concentrated SA probs: near-certain dups
    assert len(np.unique(np.asarray(pipe_wr.state.fit.landmark_idx))) <= 256


def test_per_stage_overrides_beat_config():
    """Stage constructor knobs (method/tile/backend) override the config."""
    data = krr_data.bimodal(jax.random.PRNGKey(6), 512, d=3)
    cfg = PipelineConfig(num_landmarks=16, tile=128, kde_method="binned")
    stages = [DensityStage(method="direct"), LeverageStage(), SampleStage(),
              SolveStage(tile=64)]
    pipe = SAKRRPipeline(cfg, stages=stages).fit(data.x, data.y)
    from repro.core import kde
    want = np.asarray(kde.kde_direct(data.x, data.x,
                                     kde.scott_bandwidth(data.x)))
    np.testing.assert_allclose(np.asarray(pipe.state.densities), want,
                               rtol=1e-5, atol=1e-9)


# ------------------------------------------------------- predict / score --

def test_predict_stage_matches_direct_predict_streaming():
    """PredictStage composed into the fold == nystrom.predict_streaming on
    the fitted state (bit-exact: same code path, same backend/tile)."""
    data, ctx = _ctx(seed=7)
    run_stages(evaluate_stages(None), ctx)
    assert ctx.predictions is not None and ctx.predictions.shape == (ctx.n,)
    want = np.asarray(nystrom.predict_streaming(
        ctx.kernel, ctx.fit, ctx.x, tile=ctx.config.tile))
    np.testing.assert_array_equal(np.asarray(ctx.predictions), want)
    # score stage saw the in-sample default targets
    assert set(ctx.scores) == {"mse", "rmse"}
    assert ctx.scores["rmse"] == pytest.approx(ctx.scores["mse"] ** 0.5)
    assert set(ctx.seconds) == {"kde", "leverage", "sample", "solve",
                                "predict", "score"}


def test_predict_stage_out_of_sample_and_score_targets():
    data, ctx = _ctx(seed=8)
    x_new = data.x[:100] + 0.01
    f_new = jnp.zeros((100,))
    run_stages(default_stages(None)
               + [PredictStage(x_eval=x_new), ScoreStage(f_star=f_new)], ctx)
    assert ctx.predictions.shape == (100,)
    assert "risk" in ctx.scores and "mse" not in ctx.scores  # no y_eval known
    want = np.asarray(nystrom.predict_streaming(
        ctx.kernel, ctx.fit, x_new, tile=ctx.config.tile))
    np.testing.assert_array_equal(np.asarray(ctx.predictions), want)


def test_score_stage_requires_targets_out_of_sample():
    data, ctx = _ctx(seed=9)
    stages = default_stages(None) + [PredictStage(x_eval=data.x[:50] + 1.0),
                                     ScoreStage()]
    with pytest.raises(StageError):
        run_stages(stages, ctx)


def test_score_stage_requires_predictions():
    _, ctx = _ctx(seed=10)
    with pytest.raises(StageError):
        ScoreStage()(ctx)


def test_pipeline_evaluate_one_fold():
    """SAKRRPipeline.evaluate: KDE->leverage->sample->solve->predict->score
    through one run_stages fold, timing every stage."""
    data = krr_data.bimodal(jax.random.PRNGKey(11), 4096, d=3)
    cfg = PipelineConfig(num_landmarks=128, tile=1024)
    pipe = SAKRRPipeline(cfg)
    scores = pipe.evaluate(data.x, data.y, f_star=data.f_star)
    assert set(scores) == {"mse", "rmse", "risk"}
    assert scores["risk"] < 0.05            # well under the 0.25 noise floor
    assert scores["mse"] > scores["risk"]   # mse carries the noise variance
    assert set(pipe.seconds) == {"kde", "leverage", "sample", "solve",
                                 "predict", "score"}
    # fused in-sample scoring: the solve banked the score moments in its own
    # row stream, so no predict pass ran and no predictions materialized
    assert pipe.state.predictions is None
    assert pipe.state.scores == scores


def test_weighted_solve_stage_matches_unweighted_predictor():
    """SolveStage(weighted=True) feeds ctx.sample_weights into the column-
    rescaled SoR solve; the predictor is invariant (exact arithmetic), so
    the two stage configurations must agree to fp32 whitening noise."""
    preds = []
    for weighted in (False, True):
        _, ctx = _ctx(seed=12)
        stages = [DensityStage(), LeverageStage(), SampleStage(),
                  SolveStage(weighted=weighted), PredictStage(), ScoreStage()]
        run_stages(stages, ctx)
        preds.append(np.asarray(ctx.predictions))
    np.testing.assert_allclose(preds[0], preds[1], atol=5e-2)


def test_evaluate_fold_under_forced_two_device_mesh():
    """The same evaluate() fold inside an activated 2-device mesh shards the
    solve and predict rows and must match the unsharded scores."""
    out = _run_forced_devices("""
        from repro.data import krr_data
        from repro.distributed import sharding as shd
        from repro.launch import mesh as mesh_lib
        from repro.pipeline import PipelineConfig, SAKRRPipeline
        assert jax.device_count() == 2
        data = krr_data.bimodal(jax.random.PRNGKey(0), 2048, d=3)
        cfg = PipelineConfig(num_landmarks=48, tile=512, seed=1)
        ref = SAKRRPipeline(cfg).evaluate(data.x, data.y, f_star=data.f_star)
        mesh = mesh_lib.make_local_mesh(devices=jax.devices()[:2])
        with shd.activate(mesh):
            sh = SAKRRPipeline(cfg).evaluate(data.x, data.y,
                                             f_star=data.f_star)
        assert set(sh) == {"mse", "rmse", "risk"}, sh
        for k in ref:
            np.testing.assert_allclose(sh[k], ref[k], rtol=2e-2, atol=1e-4)
        print("EVALUATE_MESH_OK")
    """)
    assert "EVALUATE_MESH_OK" in out


def test_kde_binned_sharded_d2_non_dividing_n_falls_back():
    """kde_binned_sharded at d=2 with n not divisible by the mesh size must
    degrade to the exact single-device computation (no collective)."""
    out = _run_forced_devices("""
        from repro.core import distributed as D
        from repro.data import krr_data
        from repro.distributed import sharding as shd
        from repro.launch import mesh as mesh_lib
        n, d, h = 2047, 2, 0.25          # 2047 odd: 2-device mesh cannot split
        data = krr_data.bimodal(jax.random.PRNGKey(3), n, d=d)
        lo = jnp.full((d,), -5.0); hi = jnp.full((d,), 5.0)
        ref = D.kde_binned_sharded(data.x, h, grid_size=64, lo=lo, hi=hi)
        mesh = mesh_lib.make_local_mesh(devices=jax.devices()[:2])
        with shd.activate(mesh):
            sh = D.kde_binned_sharded(data.x, h, grid_size=64, lo=lo, hi=hi)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(sh))
        # and an even n=2048 run on the same grid agrees to psum tolerance
        x_even = data.x[:2046]
        ref_e = D.kde_binned_sharded(x_even, h, grid_size=64, lo=lo, hi=hi)
        with shd.activate(mesh):
            sh_e = D.kde_binned_sharded(x_even, h, grid_size=64, lo=lo, hi=hi)
        np.testing.assert_allclose(np.asarray(ref_e), np.asarray(sh_e),
                                   rtol=2e-4, atol=1e-7)
        print("KDE_FALLBACK_OK")
    """)
    assert "KDE_FALLBACK_OK" in out
