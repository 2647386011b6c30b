"""`streaming.multi_reduce` — one tile scan driving N accumulators: plain
slots are bit-equal to sequential passes, compensated slots keep their
(hi, lo) pair through the fused scan and a forced-2-device psum, and the
fused pipeline `evaluate()` streams x at most twice (deposit + Gram) while
scoring within 2e-3 of the separate predict pass."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kernels as K, nystrom, streaming
from repro.core.kernels import kernel_matrix
from repro.data import krr_data
from repro.pipeline import stages

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KERN = K.Matern(nu=1.5)


def run_sub(body: str, env_extra: dict | None = None) -> str:
    code = textwrap.dedent("""
        import jax
        import jax.numpy as jnp
        import numpy as np
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               **(env_extra or {}))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def _gram_emits(x, xm, y):
    """Two reductions off one kernel tile: G = K^T K and rhs = K^T y."""
    def emit(xt, yt):
        k = kernel_matrix(KERN, xt, xm).astype(jnp.float32)
        return (k.T @ k, k.T @ yt)

    def emit_g(xt):
        k = kernel_matrix(KERN, xt, xm).astype(jnp.float32)
        return k.T @ k

    def emit_r(xt, yt):
        k = kernel_matrix(KERN, xt, xm).astype(jnp.float32)
        return k.T @ yt

    return emit, emit_g, emit_r


# ------------------------------------------------------------- bit parity --

def test_fused_plain_slots_bit_equal_sequential():
    """A fused plain-slot scan runs each slot's exact op sequence — the
    results match slot-by-slot sequential `tile_reduce` calls bitwise."""
    n, m = 4096, 32
    x = jax.random.normal(jax.random.PRNGKey(0), (n, 3), jnp.float32)
    y = jax.random.normal(jax.random.PRNGKey(1), (n,), jnp.float32)
    xm = x[:m]
    emit, emit_g, emit_r = _gram_emits(x, xm, y)
    inits = (jnp.zeros((m, m)), jnp.zeros((m,)))
    g_f, r_f = streaming.multi_reduce(emit, x, (y,), tile=256, inits=inits)
    g_s = streaming.tile_reduce(emit_g, x, tile=256, init=inits[0])
    r_s = streaming.tile_reduce(emit_r, x, (y,), tile=256, init=inits[1])
    assert np.array_equal(np.asarray(g_f), np.asarray(g_s))
    assert np.array_equal(np.asarray(r_f), np.asarray(r_s))


def test_fused_mixed_slots_and_errors():
    """Per-slot strategies mix freely; malformed slot counts raise."""
    n, m = 2048, 16
    x = jax.random.normal(jax.random.PRNGKey(2), (n, 3), jnp.float32)
    y = jax.random.normal(jax.random.PRNGKey(3), (n,), jnp.float32)
    xm = x[:m]
    emit, emit_g, emit_r = _gram_emits(x, xm, y)
    inits = (jnp.zeros((m, m)), jnp.zeros((m,)))
    g_f, r_f = streaming.multi_reduce(emit, x, (y,), tile=128, inits=inits,
                                      accumulators=("compensated", "plain"))
    g_s = streaming.tile_reduce(emit_g, x, tile=128, init=inits[0],
                                accumulator="compensated")
    r_s = streaming.tile_reduce(emit_r, x, (y,), tile=128, init=inits[1])
    assert np.array_equal(np.asarray(g_f), np.asarray(g_s))
    assert np.array_equal(np.asarray(r_f), np.asarray(r_s))
    with pytest.raises(ValueError):
        streaming.MultiAccumulator(("plain",), combines=(None, None))
    with pytest.raises(ValueError):
        streaming.multi_reduce(emit, x, (y,), tile=128, inits=inits,
                               accumulators=("plain",))


def test_fused_compensated_slot_state_survives_scan():
    """finalize=False exposes the per-slot states: the compensated slot is
    a live (hi, lo) pair (lo nonzero on a long stream) whose collapse equals
    the finalized fused run bitwise."""
    n, m = 32768, 24
    x = jax.random.normal(jax.random.PRNGKey(4), (n, 3), jnp.float32)
    y = jax.random.normal(jax.random.PRNGKey(5), (n,), jnp.float32)
    xm = x[:m]
    emit, _, _ = _gram_emits(x, xm, y)
    inits = (jnp.zeros((m, m)), jnp.zeros((m,)))
    state = streaming.multi_reduce(emit, x, (y,), tile=256, inits=inits,
                                   accumulators=("compensated", "plain"),
                                   finalize=False)
    (g_hi, g_lo), r_state = state
    assert float(jnp.abs(g_lo).max()) > 0.0
    g_f, r_f = streaming.multi_reduce(emit, x, (y,), tile=256, inits=inits,
                                      accumulators=("compensated", "plain"))
    assert np.array_equal(np.asarray(g_hi + g_lo), np.asarray(g_f))
    assert np.array_equal(np.asarray(r_state), np.asarray(r_f))


@pytest.mark.slow
def test_fused_compensated_slot_survives_psum():
    """Forced 2-device mesh: a MultiAccumulator with a compensated slot
    psums hi and lo separately — the sharded fused reduction matches the
    single-device one to reduction-order noise, with lo alive post-psum."""
    out = run_sub("""
        from repro.core import kernels as K, streaming
        from repro.core.kernels import kernel_matrix
        from repro.distributed import sharding as shd
        from repro.launch import mesh as mesh_lib
        assert jax.device_count() == 2, jax.devices()
        kern = K.Matern(nu=1.5)
        n, m = 32768, 24
        x = jax.random.normal(jax.random.PRNGKey(0), (n, 3))
        y = jax.random.normal(jax.random.PRNGKey(1), (n,))
        xm = x[:m]
        multi = streaming.MultiAccumulator(("compensated", "plain"))

        def local(xv, yv, xm_rep):
            def emit(xt, yt):
                k = kernel_matrix(kern, xt, xm_rep).astype(jnp.float32)
                return (k.T @ k, k.T @ yt)
            inits = (jnp.zeros((m, m)), jnp.zeros((m,)))
            return streaming.multi_reduce(
                emit, xv, (yv,), tile=512, inits=inits,
                accumulators=("compensated", "plain"), finalize=False)

        g_ref, r_ref = streaming.mesh_reduce(local, (x, y), (xm,),
                                             accumulator=multi)
        mesh = mesh_lib.make_local_mesh(devices=jax.devices()[:2])
        with shd.activate(mesh):
            state = streaming.mesh_reduce(local, (x, y), (xm,),
                                          accumulator=multi, finalize=False)
            (g_hi, g_lo), r_state = state
            g_sh, r_sh = multi.finalize(state)
        assert float(jnp.abs(g_lo).max()) > 0.0
        np.testing.assert_allclose(np.asarray(g_sh), np.asarray(g_ref),
                                   rtol=2e-6, atol=1e-4)
        np.testing.assert_allclose(np.asarray(r_sh), np.asarray(r_ref),
                                   rtol=2e-5, atol=1e-4)
        print("MULTI_PSUM_OK")
    """, env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert "MULTI_PSUM_OK" in out


# -------------------------------------------------------- fused evaluate() --

def test_evaluate_streams_x_at_most_twice(monkeypatch):
    """The fused `evaluate()` touches the full x stream exactly twice — the
    KDE deposit and the score-carrying Gram pass.  No predict pass runs."""
    from repro.pipeline import PipelineConfig, SAKRRPipeline
    n = 4096
    data = krr_data.bimodal(jax.random.PRNGKey(6), n, d=3)
    passes = []
    orig_reduce, orig_map = streaming.tile_reduce, streaming.tile_map

    def counting_reduce(emit, x, *a, **kw):
        if hasattr(x, "shape") and x.shape and x.shape[0] == n:
            passes.append("reduce")
        return orig_reduce(emit, x, *a, **kw)

    def counting_map(fn, x, *a, **kw):
        if hasattr(x, "shape") and x.shape and x.shape[0] == n:
            passes.append("map")
        return orig_map(fn, x, *a, **kw)

    monkeypatch.setattr(streaming, "tile_reduce", counting_reduce)
    monkeypatch.setattr(streaming, "tile_map", counting_map)
    pipe = SAKRRPipeline(PipelineConfig(num_landmarks=64, tile=512))
    scores = pipe.evaluate(data.x, data.y, f_star=data.f_star)
    assert set(scores) == {"mse", "rmse", "risk"}
    assert len(passes) <= 2, passes
    assert "map" not in passes   # the predict pass was fused away


def test_fused_scores_match_predict_pass():
    """Fused in-sample scoring (quadratic forms in the Gram moments,
    assembled in host f64) agrees with the separate predict-then-score fold
    to 2e-3 relative — the two big terms cancel to ~n * mse, so this locks
    ~3 surviving digits through the cancellation."""
    from repro.pipeline import (PipelineConfig, PredictStage, SAKRRPipeline,
                                ScoreStage, StageContext, default_stages,
                                run_stages)
    data = krr_data.bimodal(jax.random.PRNGKey(7), 4096, d=3)
    cfg = PipelineConfig(num_landmarks=96, tile=512)
    pipe = SAKRRPipeline(cfg)
    fused = pipe.evaluate(data.x, data.y, f_star=data.f_star)
    assert pipe.state.predictions is None

    ctx = StageContext(config=cfg, kernel=cfg.build_kernel(), x=data.x,
                       y=data.y, n=4096, d=3, lam=cfg.resolve_lam(4096),
                       num_landmarks=96, f_star=data.f_star)
    stages = default_stages(cfg) + [PredictStage(), ScoreStage()]
    run_stages(stages, ctx)
    assert ctx.predictions is not None
    for key in ("mse", "rmse", "risk"):
        np.testing.assert_allclose(fused[key], ctx.scores[key], rtol=2e-3)


def test_fit_streaming_scored_moments_match_direct():
    """The scored fit's moments reproduce the direct quadratic forms."""
    n, m = 4096, 48
    x = jax.random.normal(jax.random.PRNGKey(8), (n, 3), jnp.float32)
    y = jnp.sin(x[:, 0]) + 0.1 * jax.random.normal(jax.random.PRNGKey(9),
                                                   (n,), jnp.float32)
    idx = jnp.arange(m)
    state = nystrom.normal_eq_state(KERN, x, y[:, None], idx, tile=512)
    fit = nystrom.solve_from_state(state, 1e-3)
    mom = stages.score_moments(state, y, None)
    ref = nystrom.fit_streaming(KERN, x, y, 1e-3, idx, tile=512)
    # the scored fit's rhs is column 0 of a widened (n, 1+r) gemm — same
    # products as the (n,) gemv but a different XLA reduction order, so the
    # solve agrees to whitening noise rather than bitwise
    np.testing.assert_allclose(np.asarray(fit.beta), np.asarray(ref.beta),
                               rtol=1e-2, atol=1e-3)
    assert mom["n_eval"] == n and mom["rhs_f"] is None
    beta = np.asarray(fit.beta, np.float64)
    q = beta @ np.asarray(mom["g"], np.float64) @ beta
    mse = (q - 2.0 * beta @ np.asarray(mom["rhs_y"], np.float64)
           + mom["y_sq"]) / n
    pred = nystrom.predict_streaming(KERN, fit, x, tile=512)
    mse_ref = float(jnp.mean((pred - y) ** 2))
    np.testing.assert_allclose(mse, mse_ref, rtol=2e-3)
