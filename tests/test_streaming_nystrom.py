"""Streaming Nyström solve: parity vs the dense oracle, Pallas gram backend,
and sharded-vs-single-device equivalence on a forced 2-device host mesh.

The ≤ 1e-4 beta-parity contract is checked under enable_x64 in a subprocess
(the streaming machinery is dtype-preserving): in f64 the two paths differ
only by reduction order, ~1e-10.  In f32 the normal equations' conditioning
(~1e6 at the paper's lam) amplifies fp32 epsilon into the few-1e-3 range on
beta, so the in-process f32 checks assert the stable functional (fitted
values) instead.
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

from repro.core import kernels as K, nystrom
from repro.data import krr_data

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


# ------------------------------------------------------------------ parity --

def test_streaming_beta_parity_x64():
    """Acceptance contract: streaming beta == dense beta to <= 1e-4 relative
    (n = 4096 fixture, both XLA-scan and Pallas-gram interpret backends)."""
    out = run_sub("""
        from repro.core import kernels as K, nystrom
        n, m, d = 4096, 256, 3
        kx, ky, kw = jax.random.split(jax.random.PRNGKey(0), 3)
        x = jax.random.normal(kx, (n, d), dtype=jnp.float64)
        y = jax.random.normal(kw, (n,), dtype=jnp.float64)
        idx = jax.random.randint(ky, (m,), 0, n)   # with replacement
        kern = K.Matern(nu=1.5)
        lam = 0.075 * n ** (-2.0 / 3.0)
        dense = nystrom.fit_from_landmarks(kern, x, y, lam, idx)
        for backend, kw2 in (("xla", {}), ("pallas", dict(interpret=True))):
            st = nystrom.fit_streaming(kern, x, y, lam, idx, tile=512,
                                       backend=backend, **kw2)
            rel = float(jnp.linalg.norm(st.beta - dense.beta)
                        / jnp.linalg.norm(dense.beta))
            assert rel < 1e-4, (backend, rel)
        print("BETA_PARITY_OK")
    """, env_extra={"JAX_ENABLE_X64": "1"})
    assert "BETA_PARITY_OK" in out


def test_streaming_fitted_parity_f32():
    """f32 in-process: fitted values (the conditioning-stable functional)
    agree between streaming backends and the dense solve."""
    n, m = 2048, 128
    data = krr_data.bimodal(jax.random.PRNGKey(0), n, d=3)
    lam = 0.075 * n ** (-2.0 / 3.0)
    idx = jax.random.randint(jax.random.PRNGKey(1), (m,), 0, n)
    dense = nystrom.fit_from_landmarks(KERN, data.x, data.y, lam, idx)
    want = np.asarray(nystrom.fitted(KERN, dense, data.x))
    for backend, kw in (("xla", {}), ("pallas", dict(interpret=True))):
        st = nystrom.fit_streaming(KERN, data.x, data.y, lam, idx, tile=256,
                                   backend=backend, **kw)
        got = np.asarray(nystrom.predict_streaming(KERN, st, data.x, tile=256))
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-3,
                                   err_msg=backend)


def test_scan_normal_eq_matches_dense_gram():
    n, m = 1000, 64
    kx, ky, kw = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(kx, (n, 3))
    w = jax.random.normal(kw, (n,))
    xm = x[jax.random.permutation(ky, n)[:m]]
    k_nm = K.kernel_matrix(KERN, x, xm)
    g, r = nystrom.scan_normal_eq(KERN, x, xm, w, tile=192)  # ragged last tile
    np.testing.assert_allclose(np.asarray(g), np.asarray(k_nm.T @ k_nm),
                               rtol=2e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(r), np.asarray(k_nm.T @ w),
                               rtol=2e-5, atol=1e-3)


def test_predict_streaming_matches_dense_predict():
    n, m = 700, 48
    data = krr_data.bimodal(jax.random.PRNGKey(3), n, d=3)
    lam = 1e-3
    idx = jax.random.randint(jax.random.PRNGKey(4), (m,), 0, n)
    fit_ = nystrom.fit_from_landmarks(KERN, data.x, data.y, lam, idx)
    want = np.asarray(nystrom.predict(KERN, fit_, data.x[:333]))
    got = np.asarray(nystrom.predict_streaming(KERN, fit_, data.x[:333],
                                               tile=100))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------- sharded --

def test_sharded_streaming_matches_single_device():
    """Fake 2-device host mesh: rows sharded on the 'data' axis, Gram/rhs
    psum-reduced; equals the single-device solve up to reduction order."""
    out = run_sub("""
        import os
        from repro.core import kernels as K, nystrom
        from repro.distributed import sharding as shd
        from repro.launch import mesh as mesh_lib
        assert jax.device_count() == 2, jax.devices()
        n, m = 2048, 64
        kx, ky, kw = jax.random.split(jax.random.PRNGKey(0), 3)
        x = jax.random.normal(kx, (n, 3))
        y = jax.random.normal(kw, (n,))
        idx = jax.random.randint(ky, (m,), 0, n)
        kern = K.Matern(nu=1.5)
        lam = 1e-3
        ref = nystrom.fit_streaming(kern, x, y, lam, idx, tile=256)
        mesh = mesh_lib.make_local_mesh(devices=jax.devices()[:2])
        with shd.activate(mesh):
            sh = nystrom.fit_streaming(kern, x, y, lam, idx, tile=256)
        rel = float(jnp.linalg.norm(sh.beta - ref.beta)
                    / jnp.linalg.norm(ref.beta))
        assert rel < 2e-3, rel
        fr = nystrom.predict_streaming(kern, ref, x[:256])
        fs = nystrom.predict_streaming(kern, sh, x[:256])
        np.testing.assert_allclose(np.asarray(fs), np.asarray(fr),
                                   rtol=2e-2, atol=2e-3)
        print("SHARDED_STREAM_OK")
    """, env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert "SHARDED_STREAM_OK" in out


def test_indivisible_rows_fall_back_to_single_device():
    """n not divisible by the data axis -> the rules table drops the axis
    (replicated) and the solve still runs, matching the unsharded result."""
    out = run_sub("""
        from repro.core import kernels as K, nystrom
        from repro.distributed import sharding as shd
        from repro.launch import mesh as mesh_lib
        n, m = 1027, 32   # prime-ish: not divisible by 2
        kx, ky, kw = jax.random.split(jax.random.PRNGKey(5), 3)
        x = jax.random.normal(kx, (n, 3))
        y = jax.random.normal(kw, (n,))
        idx = jax.random.randint(ky, (m,), 0, n)
        kern = K.Matern(nu=1.5)
        ref = nystrom.fit_streaming(kern, x, y, 1e-3, idx, tile=256)
        mesh = mesh_lib.make_local_mesh(devices=jax.devices()[:2])
        with shd.activate(mesh):
            sh = nystrom.fit_streaming(kern, x, y, 1e-3, idx, tile=256)
        np.testing.assert_allclose(np.asarray(sh.beta), np.asarray(ref.beta),
                                   rtol=1e-4, atol=1e-6)
        print("FALLBACK_OK")
    """, env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert "FALLBACK_OK" in out
