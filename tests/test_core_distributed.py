"""The pipeline's sharded paths on forced host devices (subprocesses): the
sharded binned KDE against its oracle, the sharded fit's leverage against
the one-device fold (8 devices), and the sharded fit's Gram program on a
(data, model) mesh (4 devices)."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, devices: int = 8) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={devices}"
        import jax
        import jax.numpy as jnp
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"), XLA_FLAGS="")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_sharded_fit_leverage_matches_one_device():
    """SAKRRPipeline.fit on an 8-device data mesh: the SA sampling
    distribution (lattice psum, global bounds, normalised leverage) matches
    the one-device fold's."""
    out = run_sub("""
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.data import krr_data
        from repro.distributed import sharding as shd
        from repro.launch import mesh as mesh_lib
        from repro.pipeline import PipelineConfig, SAKRRPipeline

        data = krr_data.bimodal(jax.random.PRNGKey(0), 4096, d=3)
        cfg = PipelineConfig(num_landmarks=32, kde_grid_size=48, tile=512)
        ref = SAKRRPipeline(cfg).fit(data.x, data.y)

        mesh = mesh_lib.make_local_mesh(devices=jax.devices()[:8])
        x = jax.device_put(data.x, NamedSharding(mesh, P("data", None)))
        y = jax.device_put(data.y, NamedSharding(mesh, P("data")))
        with shd.activate(mesh):
            sh = SAKRRPipeline(cfg).fit(x, y)

        np.testing.assert_allclose(np.asarray(sh.state.leverage.probs),
                                   np.asarray(ref.state.leverage.probs),
                                   rtol=2e-4, atol=1e-9)
        assert np.all(np.isfinite(np.asarray(sh.state.fit.beta)))
        print("SHARDED_LEVERAGE_OK")
    """)
    assert "SHARDED_LEVERAGE_OK" in out


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
def test_sharded_fit_gram_program_all_reduces_on_2d_mesh():
    """SAKRRPipeline.fit on a (2, 2) (data, model) mesh runs, and the Gram
    program it compiles all-reduces G (m, m) and rhs (m,) across the data
    axis."""
    out = run_sub("""
        import re
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import nystrom, streaming
        from repro.data import krr_data
        from repro.distributed import sharding as shd
        from repro.launch import mesh as mesh_lib
        from repro.pipeline import PipelineConfig, SAKRRPipeline

        calls = []
        program = streaming._program

        def recording(local, *a, **kw):
            fn = program(local, *a, **kw)

            def call(*args):
                calls.append((local, fn, args))
                return fn(*args)
            return call

        streaming._program = recording
        m = 32
        data = krr_data.bimodal(jax.random.PRNGKey(1), 4096, d=3)
        cfg = PipelineConfig(num_landmarks=m, kde_grid_size=32, tile=512)
        mesh = mesh_lib.make_local_mesh_2d(model_parallelism=2)
        assert mesh.shape == {"data": 2, "model": 2}
        x = jax.device_put(data.x, NamedSharding(mesh, P("data", None)))
        y = jax.device_put(data.y, NamedSharding(mesh, P("data")))
        with shd.activate(mesh):
            pipe = SAKRRPipeline(cfg).fit(x, y)
        assert np.all(np.isfinite(np.asarray(pipe.state.fit.beta)))

        (fn, args), = [(fn, args) for local, fn, args in calls
                       if getattr(local, "func", None)
                       is nystrom._gram_local]
        hlo = fn.lower(*args).compile().as_text()
        reduced = " ".join(line.split("all-reduce(")[0]
                           for line in hlo.splitlines()
                           if "all-reduce(" in line)
        assert re.search(rf"f32\\[{m},{m}\\]", reduced), reduced
        assert re.search(rf"f32\\[{m}\\]", reduced), reduced
        print("GRAM_ALL_REDUCE_OK")
    """, devices=4)
    assert "GRAM_ALL_REDUCE_OK" in out
