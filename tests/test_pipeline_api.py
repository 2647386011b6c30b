"""repro.pipeline: config round-trip, end-to-end fit/predict quality, and
the O(tile · m) memory contract surface (tile invariance)."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import krr, nystrom
from repro.data import krr_data
from repro.pipeline import PipelineConfig, SAKRRPipeline


def test_config_roundtrip_and_defaults():
    cfg = PipelineConfig(nu=2.5, tile=1024, num_landmarks=64)
    again = PipelineConfig.from_dict(cfg.to_dict())
    assert again == cfg
    n = 8000
    assert cfg.resolve_lam(n) == pytest.approx(0.075 * n ** (-2.0 / 3.0))
    assert PipelineConfig().resolve_num_landmarks(n) == int(5 * n ** (1 / 3))
    assert PipelineConfig(kernel_kind="gaussian", sigma=0.5).build_kernel().sigma == 0.5
    with pytest.raises(ValueError):
        PipelineConfig(kernel_kind="laplace").build_kernel()


def test_config_json_roundtrip_restores_tuples():
    """JSON turns lam_grid/h_grid tuples into lists; from_dict must restore
    them (frozen-dataclass equality/hash) — the servable-artifact contract."""
    cfg = PipelineConfig(lam_grid=(1e-3, 1e-2), h_grid=(0.1, 0.2, 0.4),
                         num_landmarks=64)
    again = PipelineConfig.from_dict(json.loads(json.dumps(cfg.to_dict())))
    assert again == cfg
    assert hash(again) == hash(cfg)
    assert isinstance(again.lam_grid, tuple)
    assert isinstance(again.h_grid, tuple)
    # None grids stay None through the round trip
    none_cfg = PipelineConfig()
    assert PipelineConfig.from_dict(
        json.loads(json.dumps(none_cfg.to_dict()))) == none_cfg


def test_config_from_dict_rejects_unknown_keys():
    d = PipelineConfig().to_dict()
    d["not_a_field"] = 1
    with pytest.raises(ValueError, match="unknown PipelineConfig key"):
        PipelineConfig.from_dict(d)


def test_predict_is_reentrant_and_preserves_evaluate_state():
    """Regression: predict used to fold over the SAVED fitted context,
    clobbering evaluate()'s scores/predictions and letting interleaved
    predict calls corrupt each other via the shared snapshot."""
    data = krr_data.bimodal(jax.random.PRNGKey(7), 1024, d=3)
    pipe = SAKRRPipeline(PipelineConfig(num_landmarks=48, tile=512))
    scores = pipe.evaluate(data.x, data.y, f_star=data.f_star)
    eval_preds = np.asarray(pipe.state.predictions) \
        if pipe.state.predictions is not None else None

    qa = data.x[:100]
    qb = data.x[100:150] + 0.25
    pa_first = np.asarray(pipe.predict(qa))
    pb = np.asarray(pipe.predict(qb))
    pa_second = np.asarray(pipe.predict(qa))
    # interleaved predicts are independent: same query, same answer
    np.testing.assert_array_equal(pa_first, pa_second)
    assert pb.shape == (50,)
    # the evaluate() snapshot survives any number of predicts
    assert pipe.state.scores == scores
    if eval_preds is not None:
        np.testing.assert_array_equal(
            np.asarray(pipe.state.predictions), eval_preds)


def test_pipeline_fit_quality_bimodal():
    """End-to-end risk well under the 0.25 noise floor (paper's setting)."""
    n = 8192
    data = krr_data.bimodal(jax.random.PRNGKey(0), n, d=3)
    pipe = SAKRRPipeline(PipelineConfig(tile=2048)).fit(data.x, data.y)
    assert set(pipe.seconds) == {"kde", "leverage", "sample", "solve"}
    risk = float(krr.in_sample_risk(pipe.fitted(data.x), data.f_star))
    assert risk < 0.05, risk
    assert pipe.d_stat > 1.0
    # predict runs through the same stage fold, so it times itself too
    assert set(pipe.seconds) == {"kde", "leverage", "sample", "solve",
                                 "predict"}
    assert all(v >= 0.0 for v in pipe.seconds.values())


def test_pipeline_tile_invariance():
    """The tile size is an execution detail: results must not depend on it
    beyond fp32 reduction order."""
    n = 2048
    data = krr_data.bimodal(jax.random.PRNGKey(1), n, d=3)
    cfgs = [PipelineConfig(tile=t, num_landmarks=48, seed=3) for t in (256, 2048)]
    preds = []
    for cfg in cfgs:
        pipe = SAKRRPipeline(cfg).fit(data.x, data.y)
        preds.append(np.asarray(pipe.predict(data.x[:400])))
    # fp32 reduction order shifts the solve's spectral cutoff slightly;
    # predictions stay within ~1e-3 absolute on O(1)-scale targets.
    np.testing.assert_allclose(preds[0], preds[1], rtol=1e-2, atol=2e-3)


def test_pipeline_predict_matches_dense_nystrom():
    """The pipeline's solve is nystrom.fit_streaming on SA-sampled landmarks;
    its predictions must match the dense solve on the same landmarks."""
    n = 2048
    data = krr_data.bimodal(jax.random.PRNGKey(2), n, d=3)
    pipe = SAKRRPipeline(PipelineConfig(num_landmarks=64, tile=512)).fit(
        data.x, data.y)
    st = pipe.state
    dense = nystrom.fit_from_landmarks(pipe.kernel, data.x, data.y, st.lam,
                                       st.fit.landmark_idx)
    want = np.asarray(nystrom.predict(pipe.kernel, dense, data.x[:300]))
    got = np.asarray(pipe.predict(data.x[:300]))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-3)


def test_pipeline_predict_before_fit_raises():
    with pytest.raises(RuntimeError):
        SAKRRPipeline().predict(jnp.zeros((4, 3)))


def test_pipeline_gaussian_kernel_path():
    n = 1500
    data = krr_data.bimodal(jax.random.PRNGKey(4), n, d=3)
    cfg = PipelineConfig(kernel_kind="gaussian", sigma=0.6, num_landmarks=48,
                         tile=512)
    pipe = SAKRRPipeline(cfg).fit(data.x, data.y)
    risk = float(krr.in_sample_risk(pipe.fitted(data.x), data.f_star))
    assert np.isfinite(risk) and risk < 0.25, risk


def test_pipeline_fit_under_active_mesh_matches_single_device():
    """The same fit call, inside an activated 2-device mesh, shards the
    solve rows on the 'data' axis and must match the unsharded run."""
    import os
    import subprocess
    import sys
    import textwrap

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = textwrap.dedent("""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.data import krr_data
        from repro.distributed import sharding as shd
        from repro.launch import mesh as mesh_lib
        from repro.pipeline import PipelineConfig, SAKRRPipeline
        assert jax.device_count() == 2
        data = krr_data.bimodal(jax.random.PRNGKey(0), 2048, d=3)
        cfg = PipelineConfig(num_landmarks=48, tile=512, seed=1)
        ref = SAKRRPipeline(cfg).fit(data.x, data.y).predict(data.x[:256])
        mesh = mesh_lib.make_local_mesh(devices=jax.devices()[:2])
        with shd.activate(mesh):
            sh = SAKRRPipeline(cfg).fit(data.x, data.y).predict(data.x[:256])
        np.testing.assert_allclose(np.asarray(sh), np.asarray(ref),
                                   rtol=2e-2, atol=2e-3)
        print("PIPELINE_MESH_OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PIPELINE_MESH_OK" in out.stdout


def test_pipeline_state_is_small():
    """fit must keep only O(n) vectors and O(m) solve state — no (n, m)."""
    n = 1024
    data = krr_data.bimodal(jax.random.PRNGKey(5), n, d=3)
    pipe = SAKRRPipeline(PipelineConfig(num_landmarks=32, tile=256)).fit(
        data.x, data.y)
    st = pipe.state
    leaves = jax.tree.leaves((st.densities, tuple(st.leverage),
                              tuple(st.fit)))
    biggest = max(leaf.size for leaf in leaves if hasattr(leaf, "size"))
    assert biggest <= max(n, st.num_landmarks ** 2), biggest
