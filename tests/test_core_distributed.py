"""Sharded SA+Nyström pipeline == single-device reference (subprocess,
8 forced host devices), plus an abstract lowering check."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str) -> str:
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import jax.numpy as jnp
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"), XLA_FLAGS="")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_sharded_pipeline_matches_reference():
    out = run_sub("""
        import numpy as np
        from repro.core import distributed as D
        from repro.core import kernels as K
        from repro.core import kde as core_kde
        from repro.core import leverage, nystrom
        from repro.data import krr_data
        from repro.distributed import sharding as shd
        from repro.launch import mesh as mesh_lib

        n, d, m, m_kde = 1024, 3, 32, 256
        lam = 0.075 * n ** (-2/3)
        h = 0.3
        data = krr_data.bimodal(jax.random.PRNGKey(0), n, d=d)
        kde_sample = data.x[:m_kde]
        idx = jnp.arange(0, n, n // m)[:m]
        kern = K.Matern(nu=1.5)
        fn = D.make_pipeline_fn(kern, lam, h)

        # single-device reference
        ref = jax.jit(fn)(data.x, data.y, kde_sample, idx)

        mesh = mesh_lib.make_local_mesh(devices=jax.devices()[:8])
        with shd.activate(mesh, {"batch": ("data",)}):
            sh = jax.jit(fn)(data.x, data.y, kde_sample, idx)

        np.testing.assert_allclose(np.asarray(ref.probs), np.asarray(sh.probs),
                                   rtol=2e-4, atol=1e-9)
        # beta itself is near-null-space sensitive (fp32 normal equations);
        # the stable functionals are the predictions and d_stat
        np.testing.assert_allclose(np.asarray(ref.fitted),
                                   np.asarray(sh.fitted), rtol=1e-2, atol=1e-3)
        np.testing.assert_allclose(float(ref.d_stat), float(sh.d_stat),
                                   rtol=1e-5)

        # the pipeline's density/leverage agree with the core (host) path
        p_core = core_kde.kde_direct(data.x, kde_sample, h)
        sa = leverage.sa_leverage(p_core, lam, kern, d, n=n)
        np.testing.assert_allclose(np.asarray(sh.probs), np.asarray(sa.probs),
                                   rtol=2e-4, atol=1e-9)
        print("PIPELINE_MATCH_OK")
    """)
    assert "PIPELINE_MATCH_OK" in out


@pytest.mark.slow
def test_binned_kde_sharded_matches_oracle():
    out = run_sub("""
        import numpy as np
        from repro.core import distributed as D
        from repro.core import kde as core_kde
        from repro.data import krr_data
        from repro.distributed import sharding as shd
        from repro.launch import mesh as mesh_lib

        n, d, h = 2048, 3, 0.25
        data = krr_data.bimodal(jax.random.PRNGKey(3), n, d=d)
        lo = jnp.full((d,), -5.0); hi = jnp.full((d,), 5.0)

        # oracle: single-device binned KDE on the same fixed grid bounds
        from repro.kernels.kde_binned import ref as kb_ref
        spacing = (hi - lo) / (96 - 1)
        grid = kb_ref.binned_grid(data.x, lo, spacing, 96)
        smooth = core_kde._fft_smooth(grid, spacing, jnp.float32(h), 96, d)

        ref = D.kde_binned_sharded(data.x, h, grid_size=96, lo=lo, hi=hi)

        mesh = mesh_lib.make_local_mesh(devices=jax.devices()[:8])
        with shd.activate(mesh, {"batch": ("data",)}):
            sh = jax.jit(lambda x: D.kde_binned_sharded(
                x, h, grid_size=96, lo=lo, hi=hi))(data.x)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(sh),
                                   rtol=2e-4, atol=1e-7)
        # sanity vs direct KDE: binned approximation within a few percent
        direct = core_kde.kde_direct(data.x, data.x, h)
        rel = np.abs(np.asarray(sh) - np.asarray(direct)) / (
            np.asarray(direct) + 1e-9)
        assert np.quantile(rel, 0.9) < 0.05, np.quantile(rel, 0.9)
        print("BINNED_SHARDED_OK")
    """)
    assert "BINNED_SHARDED_OK" in out


@pytest.mark.slow
def test_pipeline_lowers_on_production_like_mesh():
    out = run_sub("""
        from repro.core import distributed as D
        from repro.launch import mesh as mesh_lib
        from repro.roofline import analysis as roofline
        mesh = mesh_lib.make_local_mesh_2d(model_parallelism=4)
        lowered, compiled = D.lower_pipeline(mesh, n=65536, d=3)
        cost = roofline.cost_dict(compiled)
        assert cost.get("flops", 0) > 0
        txt = compiled.as_text()
        assert "all-reduce" in txt  # the K_nm^T K_nm reduction
        print("PIPELINE_LOWER_OK", int(cost["flops"]))
    """)
    assert "PIPELINE_LOWER_OK" in out
