"""CalibrateStage subsystem: multi-lam solve parity vs per-lam fit loops
(weighted and unweighted), shared-deposit KDE parity vs per-h `kde_binned`,
the calibrate fold's grid/rewrite contract, and (forced 2 devices) that the
sweep under a mesh accumulates ONE Gram per bandwidth and ONE deposit total.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kde, kernels as K, nystrom
from repro.data import krr_data
from repro.pipeline import (CalibrateStage, PipelineConfig, SAKRRPipeline,
                            StageContext)
from repro.pipeline.stages import DEFAULT_H_FACTORS, DEFAULT_LAM_FACTORS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LAMS = [1e-2, 1e-3, 1e-4, 1e-5]


def _data(n=2048, d=3, seed=0):
    return krr_data.bimodal(jax.random.PRNGKey(seed), n, d=d)


def _landmarks(n, m=64):
    return jnp.arange(0, n, n // m)[:m]


# ------------------------------------------------------------ multi-lam fit --

def test_solve_normal_eq_multi_bit_matches_single():
    data = _data()
    kern = K.Matern(nu=1.5)
    idx = _landmarks(2048)
    xm = data.x[idx]
    g, rhs = nystrom.streaming_normal_eq(kern, data.x, data.y, xm, tile=512)
    k_mm = K.kernel_matrix(kern, xm).astype(g.dtype)
    betas = nystrom.solve_normal_eq(g, rhs, k_mm, 2048, LAMS)
    assert betas.shape == (len(LAMS), 64)
    for i, lam in enumerate(LAMS):
        want = nystrom.solve_normal_eq(g, rhs, k_mm, 2048, lam)
        np.testing.assert_array_equal(np.asarray(betas[i]), np.asarray(want))


@pytest.mark.parametrize("weighted", [False, True])
def test_fit_streaming_multi_bit_matches_per_lam_loop(weighted):
    """One shared Gram accumulation + per-lam whitened solves must be
    bit-equal to L independent fit_streaming calls (same op sequence)."""
    data = _data(seed=1)
    kern = K.Matern(nu=1.5)
    idx = _landmarks(2048)
    w = (1.0 + jnp.arange(64, dtype=jnp.float32) / 16.0) if weighted else None
    state = nystrom.normal_eq_state(kern, data.x, data.y, idx, tile=512)
    grid = nystrom.solve_from_state(state, LAMS, weights=w)
    assert list(grid.lam) == LAMS
    for i, lam in enumerate(LAMS):
        ref = nystrom.fit_streaming(kern, data.x, data.y, lam, idx,
                                    tile=512, weights=w)
        np.testing.assert_array_equal(np.asarray(grid.beta[i]),
                                      np.asarray(ref.beta))


def test_predict_streaming_multi_matches_per_fit():
    data = _data(seed=2)
    kern = K.Matern(nu=1.5)
    idx = _landmarks(2048)
    state = nystrom.normal_eq_state(kern, data.x, data.y, idx, tile=512)
    grid = nystrom.solve_from_state(state, LAMS)
    # one kernel tile per slab, all L betas applied as one (m, L) matmul
    preds = nystrom.predict_streaming(kern, grid._replace(beta=grid.beta.T),
                                      data.x[:300], tile=128).T
    assert preds.shape == (len(LAMS), 300)
    for i, lam in enumerate(LAMS):
        fit = nystrom.solve_from_state(state, lam)
        want = np.asarray(nystrom.predict_streaming(kern, fit, data.x[:300],
                                                    tile=128))
        np.testing.assert_allclose(np.asarray(preds[i]), want,
                                   rtol=1e-5, atol=1e-6)


# --------------------------------------------------------- shared-deposit --

def test_kde_binned_multi_matches_per_h():
    """One CIC deposit + per-h FFT/gather == independent kde_binned calls
    pinned to the same (max-h) grid bounds."""
    data = _data(n=4096, seed=3)
    hs = [0.12, 0.2, 0.35]
    multi = kde.kde_binned_multi(data.x, data.x, hs, grid_size=64)
    assert multi.shape == (3, 4096)
    lo, hi = kde.binned_bounds(data.x, data.x,
                               jnp.asarray(max(hs), data.x.dtype))
    for i, h in enumerate(hs):
        single = kde.kde_binned(data.x, data.x, h, grid_size=64, lo=lo, hi=hi)
        np.testing.assert_allclose(np.asarray(multi[i]), np.asarray(single),
                                   rtol=1e-5, atol=1e-12)


def test_kde_binned_default_bounds_unchanged():
    """The single-h public entry must still use its own +-4h bounds (the
    pre-refactor contract) when lo/hi are not pinned."""
    data = _data(n=2048, seed=4)
    got = kde.kde_binned(data.x, data.x, 0.25, grid_size=96)
    ref = kde.kde_direct(data.x, data.x, 0.25)
    rel = np.abs(np.asarray(got) - np.asarray(ref)) / (np.asarray(ref) + 1e-9)
    assert np.quantile(rel, 0.9) < 0.05


# ------------------------------------------------------------- stage fold --

def test_calibrate_stage_contract():
    """Grid sizes, exactly one best record, lam/bandwidth rewritten to the
    winner, downstream artifacts invalidated, per-h seconds recorded."""
    data = _data(n=2048, seed=5)
    cfg = PipelineConfig(num_landmarks=48, tile=512)
    ctx = StageContext(config=cfg, kernel=cfg.build_kernel(), x=data.x,
                       y=data.y, n=2048, d=3, lam=cfg.resolve_lam(2048),
                       num_landmarks=48)
    lam0 = ctx.lam
    CalibrateStage()(ctx)
    n_cand = len(DEFAULT_LAM_FACTORS) * len(DEFAULT_H_FACTORS)
    assert len(ctx.cv_scores) == n_cand
    best = [r for r in ctx.cv_scores if r["best"]]
    assert len(best) == 1
    assert best[0]["val_mse"] == min(r["val_mse"] for r in ctx.cv_scores)
    assert ctx.cv_best["lam"] == best[0]["lam"] == ctx.lam
    assert ctx.cv_best["bandwidth"] == best[0]["h"] == ctx.bandwidth
    # the swept lam grid brackets the paper-rate reference
    assert any(abs(r["lam"] - lam0) < 1e-12 for r in ctx.cv_scores)
    # downstream artifacts were invalidated for the calibrated refit
    assert ctx.densities is None and ctx.fit is None
    per_h = [k for k in ctx.seconds if k.startswith("calibrate[h=")]
    assert len(per_h) == len(DEFAULT_H_FACTORS)
    assert "calibrate" in ctx.seconds and "calibrate[kde]" in ctx.seconds


def test_calibrate_stage_explicit_grids_and_fraction():
    data = _data(n=1024, seed=6)
    cfg = PipelineConfig(num_landmarks=32, tile=256)
    ctx = StageContext(config=cfg, kernel=cfg.build_kernel(), x=data.x,
                       y=data.y, n=1024, d=3, lam=cfg.resolve_lam(1024),
                       num_landmarks=32)
    stage = CalibrateStage(lam_grid=[1e-3, 1e-4], h_grid=[0.2],
                           val_fraction=0.5)
    stage(ctx)
    assert len(ctx.cv_scores) == 2
    assert {r["h"] for r in ctx.cv_scores} == {0.2}
    assert ctx.lam in (1e-3, 1e-4) and ctx.bandwidth == 0.2


def test_config_grids_feed_calibrate_stage():
    data = _data(n=1024, seed=7)
    cfg = PipelineConfig(num_landmarks=32, tile=256,
                         lam_grid=(1e-3, 1e-4, 1e-5), h_grid=(0.15, 0.3))
    ctx = StageContext(config=cfg, kernel=cfg.build_kernel(), x=data.x,
                       y=data.y, n=1024, d=3, lam=cfg.resolve_lam(1024),
                       num_landmarks=32)
    CalibrateStage()(ctx)
    assert len(ctx.cv_scores) == 6
    assert {r["lam"] for r in ctx.cv_scores} == {1e-3, 1e-4, 1e-5}


def test_pipeline_calibrate_end_to_end():
    """SAKRRPipeline.calibrate: sweep + full refit at the winner in one
    fold; the calibrated in-sample risk must not lose to the paper-rate
    default by more than noise (both evaluated on the same points)."""
    data = _data(n=4096, seed=8)
    cfg = PipelineConfig(num_landmarks=96, tile=1024)
    pipe = SAKRRPipeline(cfg)
    out = pipe.calibrate(data.x, data.y, f_star=data.f_star)
    assert set(out) >= {"lam", "bandwidth", "val_mse", "cv_scores", "scores"}
    assert pipe.state.lam == out["lam"]
    assert pipe.state.cv_best["lam"] == out["lam"]
    assert pipe.state.fit is not None and pipe.state.fit.lam == out["lam"]
    assert "risk" in out["scores"]
    ref = SAKRRPipeline(cfg).evaluate(data.x, data.y, f_star=data.f_star)
    assert out["scores"]["risk"] <= ref["risk"] * 1.5


def test_calibrate_shares_one_gumbel_race_across_h():
    """The bandwidth grid's landmark draws must all receive the SAME
    precomputed Gumbel race (gumbel=), so the h axis of the sweep carries
    zero sampling noise (ROADMAP gap (e)): candidates differ only through
    their density-driven probs."""
    from repro.core import sampling as sampling_mod
    from repro.pipeline import stages as stages_mod

    data = _data(n=1024, seed=9)
    cfg = PipelineConfig(num_landmarks=32, tile=256, h_grid=(0.15, 0.3, 0.6))
    ctx = StageContext(config=cfg, kernel=cfg.build_kernel(), x=data.x,
                       y=data.y, n=1024, d=3, lam=cfg.resolve_lam(1024),
                       num_landmarks=32)
    seen: list = []
    real = sampling_mod.sample_weighted_without_replacement

    def spy(key, probs, m, **kw):
        seen.append(kw.get("gumbel"))
        return real(key, probs, m, **kw)

    stages_mod.sampling.sample_weighted_without_replacement = spy
    try:
        CalibrateStage()(ctx)
    finally:
        stages_mod.sampling.sample_weighted_without_replacement = real
    assert len(seen) == 3                      # one draw per h candidate
    assert all(g is not None for g in seen)    # explicit shared race
    assert all(g is seen[0] for g in seen)     # the SAME noise object


# ------------------------------------------------------------ mesh sharing --

def test_calibrate_fold_under_mesh_shares_gram_and_deposit():
    """Forced 2 devices: the whole (lam x h) sweep must run ONE deposit
    (and its single grid psum) total and ONE Gram accumulation (its psum)
    per bandwidth — not one per candidate — and still match the unsharded
    fold's selection."""
    body = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.data import krr_data
        from repro.distributed import sharding as shd
        from repro.launch import mesh as mesh_lib
        from repro.core import nystrom
        from repro.kernels import dispatch
        from repro.pipeline import CalibrateStage, PipelineConfig, StageContext

        assert jax.device_count() == 2
        n = 2048
        data = krr_data.bimodal(jax.random.PRNGKey(0), n, d=3)
        cfg = PipelineConfig(num_landmarks=48, tile=512,
                             lam_grid=(1e-3, 1e-4, 1e-5), h_grid=(0.15, 0.3))

        def ctx():
            return StageContext(config=cfg, kernel=cfg.build_kernel(),
                                x=data.x, y=data.y, n=n, d=3,
                                lam=cfg.resolve_lam(n), num_landmarks=48)

        # val_fraction 0.25 -> n_tr = 1536 already divides the 2-device
        # mesh, so sharded and unsharded runs see the IDENTICAL split (the
        # stage would otherwise grow the holdout to restore divisibility and
        # the folds would not be comparable candidate-by-candidate)
        stage = lambda: CalibrateStage(val_fraction=0.25)

        # Gram streams are counted where each one is dispatched: under a
        # mesh the stream is a compiled program that later streams of the
        # same shapes reuse without running its Python body again
        counts = {"gram": 0, "scatter": 0}
        real_gram = nystrom.streaming_normal_eq
        real_scatter = dispatch.binned_scatter
        def gram(*a, **k):
            counts["gram"] += 1
            return real_gram(*a, **k)
        def scatter(*a, **k):
            counts["scatter"] += 1
            return real_scatter(*a, **k)
        nystrom.streaming_normal_eq = gram
        dispatch.binned_scatter = scatter

        c_ref = ctx(); stage()(c_ref)
        ref_counts = dict(counts)
        counts.update(gram=0, scatter=0)
        mesh = mesh_lib.make_local_mesh(devices=jax.devices()[:2])
        with shd.activate(mesh):
            c_sh = ctx(); stage()(c_sh)

        # shared work: one Gram stream per h (2), one deposit for the sweep
        assert counts["gram"] == 2, counts
        assert counts["scatter"] == 1, counts
        assert ref_counts == counts, (ref_counts, counts)

        # identical fold -> per-candidate scores differ only by psum
        # reduction order, and the winner's quality matches
        for a, b in zip(c_ref.cv_scores, c_sh.cv_scores):
            np.testing.assert_allclose(a["val_mse"], b["val_mse"],
                                       rtol=1e-3)
        np.testing.assert_allclose(c_sh.cv_best["val_mse"],
                                   c_ref.cv_best["val_mse"], rtol=1e-3)
        print("CALIBRATE_MESH_OK")
    """
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         capture_output=True, text=True, env=env, timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "CALIBRATE_MESH_OK" in out.stdout
