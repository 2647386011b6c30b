"""2D (data x model) mesh semantics under forced host devices.

Each test forks a fresh interpreter with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (jax locks the
device count at backend init, so the parent process cannot host these)
and builds a (2, 2) (data, model) grid via `launch.mesh.make_local_mesh_2d`.

The locked contracts:
  * data axes psum, the model axis shards independent work — per-h KDE
    densities and per-lam whitened solves on the 2D mesh are BIT-equal to
    the 1D data-mesh path with the same data-shard count;
  * `streaming.row_shard_count` counts data-axis shards only (the
    eps_scale step budget must not inflate with model parallelism), and
    `streaming.model_shard_count` counts the model axis;
  * the compensated (hi, lo) accumulator pair and `accstate.psum` survive
    the data-axis-only reduction un-collapsed;
  * `nystrom.fit_streaming_batched` / `predict_streaming_batched` match
    the per-model python loop, meshless and sharded.
"""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_forced(body: str, marker: str, devices: int = 4,
                timeout: int = 500) -> None:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    assert marker in out.stdout, out.stdout[-2000:]


def test_kde_multi_2d_mesh_bit_equal_to_1d():
    """Per-h densities on the (2, 2) mesh are bit-equal to the 2-device 1D
    data mesh: same deposit participants, same per-h op sequence (the
    bandwidth is sliced from a sharded device array on the 2D path)."""
    body = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed import sharding as shd
        from repro.core import distributed as dist, streaming
        from repro.launch import mesh as mesh_lib

        assert jax.device_count() == 4
        x = jax.random.normal(jax.random.PRNGKey(0), (256, 2), jnp.float32)
        hs = [0.2, 0.3, 0.5, 0.8]

        mesh1 = mesh_lib.make_local_mesh(devices=jax.devices()[:2])
        with shd.activate(mesh1):
            ref = np.asarray(dist.kde_binned_sharded_multi(x, hs,
                                                           grid_size=32))
            assert streaming.row_shard_count(x.shape) == 2
            assert streaming.model_shard_count(len(hs)) == 1

        mesh2 = mesh_lib.make_local_mesh_2d(model_parallelism=2)
        with shd.activate(mesh2):
            out = np.asarray(dist.kde_binned_sharded_multi(x, hs,
                                                           grid_size=32))
            # row_shard_count: DATA axis only; the model axis must not
            # inflate the eps_scale step budget
            assert streaming.row_shard_count(x.shape) == 2
            assert streaming.model_shard_count(len(hs)) == 2

        np.testing.assert_array_equal(ref, out)
        print("KDE2D_BITEQ_OK")
    """
    _run_forced(body, "KDE2D_BITEQ_OK")


def test_solve_multi_2d_mesh_bit_equal_to_1d():
    """The model-sharded multi-lam whitened solve (2 lams per chip column)
    is bit-equal to the 1D-mesh replicated body (4 lams per chip): the
    per-lam op chain compiles identically regardless of the local count."""
    body = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import nystrom
        from repro.core.kernels import Gaussian, kernel_matrix
        from repro.distributed import sharding
        from repro.launch import mesh as mesh_lib

        rng = np.random.default_rng(0)
        n, d, m = 512, 3, 16
        x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        y = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
        kern = Gaussian(1.0)
        idx = jnp.asarray(rng.integers(0, n, size=(m,)))
        xm = x[idx]
        k_nm = kernel_matrix(kern, x, xm)
        g = (k_nm.T @ k_nm).astype(jnp.float32)
        rhs = k_nm.T @ y
        k_mm = kernel_matrix(kern, xm)
        lam_grid = [1e-3, 3e-3, 1e-2, 3e-2]

        eager = nystrom.solve_normal_eq(g, rhs, k_mm, n, lam_grid)
        with sharding.activate(mesh_lib.make_local_mesh()):
            ref = nystrom.solve_normal_eq(g, rhs, k_mm, n, lam_grid)
        with sharding.activate(mesh_lib.make_local_mesh_2d(2)):
            shd = nystrom.solve_normal_eq(g, rhs, k_mm, n, lam_grid)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(shd))
        # the meshless eager loop differs only by jit-time FMA fusion
        np.testing.assert_allclose(np.asarray(eager), np.asarray(ref),
                                   rtol=1e-4, atol=1e-6)
        print("SOLVE2D_BITEQ_OK")
    """
    _run_forced(body, "SOLVE2D_BITEQ_OK")


def test_streaming_primitives_2d_mesh():
    """mesh_reduce/mesh_map model_args layouts; the compensated (hi, lo)
    pair and `accstate.psum` cross the data-axis-only psum un-collapsed
    (1D and (2, 2) results bit-equal: same data-shard participants)."""
    body = """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import accstate, streaming
        from repro.distributed import sharding as shd
        from repro.launch import mesh as mesh_lib

        mesh1 = mesh_lib.make_local_mesh(devices=jax.devices()[:2])
        mesh2 = mesh_lib.make_local_mesh_2d(model_parallelism=2)
        rows = jax.random.normal(jax.random.PRNGKey(1), (64,), jnp.float32)
        w = jnp.arange(1.0, 5.0)

        # mesh_reduce(model_args=): per-model reductions over shared rows
        def local(r_loc, w_loc):
            return jax.vmap(lambda wi: wi * jnp.sum(r_loc))(w_loc)
        with shd.activate(mesh2):
            got = streaming.mesh_reduce(local, (rows,), model_args=(w,))
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(w) * float(jnp.sum(rows)),
                                   rtol=1e-6)

        # compensated pair: 1D vs 2D bit-equal (data psum only)
        def local_c(r_loc):
            return streaming.tile_reduce(lambda t: jnp.sum(t), r_loc,
                                         tile=16, init=jnp.zeros(()),
                                         accumulator="compensated",
                                         pad="zero", finalize=False)
        with shd.activate(mesh1):
            s1 = streaming.mesh_reduce(local_c, (rows,),
                                       accumulator="compensated")
        with shd.activate(mesh2):
            s2 = streaming.mesh_reduce(local_c, (rows,),
                                       accumulator="compensated")
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))

        # mesh_map(model_args=): (B, n) batched map layout
        x = jax.random.normal(jax.random.PRNGKey(2), (256, 2), jnp.float32)
        def mloc(x_loc, w_loc):
            return jax.vmap(lambda wi: wi * x_loc[:, 0])(w_loc)
        with shd.activate(mesh2):
            mm = streaming.mesh_map(mloc, x, model_args=(w,), out_rank=2)
        np.testing.assert_allclose(
            np.asarray(mm),
            np.asarray(w)[:, None] * np.asarray(x[:, 0])[None, :],
            rtol=1e-6)

        # accstate.psum: value through the strategy psum, rows sum,
        # steps max — inside a shard_map over the data axis of the 2D mesh
        def body(xs):
            st = accstate.init("compensated", jnp.zeros((), jnp.float32),
                               rows=xs.shape[0], steps=1)
            hi, lo = st.value
            st = accstate.AccState(value=(hi + jnp.sum(xs), lo),
                                   rows=st.rows, steps=st.steps,
                                   spec=st.spec)
            return accstate.psum(st, ("data",))
        vec = jnp.arange(8, dtype=jnp.float32)
        out = jax.shard_map(body, mesh=mesh2, in_specs=P("data"),
                            out_specs=P())(vec)
        assert float(accstate.finalize(out)) == float(jnp.sum(vec))
        assert accstate.rows_of(out) == 8.0
        assert accstate.steps_of(out) == 1
        print("STREAM2D_OK")
    """
    _run_forced(body, "STREAM2D_OK")


def test_batched_fit_predict_2d_mesh():
    """fit_streaming_batched matches the per-model fit_streaming loop
    (meshless, <1e-4 rel) and stays within psum reduction-order tolerance
    under the (2, 2) mesh; predict_streaming_batched matches per-model
    predict_streaming in both regimes."""
    body = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import nystrom
        from repro.core.kernels import Gaussian
        from repro.distributed import sharding
        from repro.launch import mesh as mesh_lib

        rng = np.random.default_rng(0)
        n, d, m, B = 512, 3, 16, 4
        x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        ys = jnp.asarray(rng.normal(size=(B, n)), jnp.float32)
        kern = Gaussian(1.0)
        lams = jnp.asarray([1e-3, 3e-3, 1e-2, 3e-2], jnp.float32)
        lsets = jnp.asarray(rng.integers(0, n, size=(B, m)))

        bf = nystrom.fit_streaming_batched(kern, x, ys, lams, lsets,
                                           tile=128)
        for b in range(B):
            f = nystrom.fit_streaming(kern, x, ys[b], float(lams[b]),
                                      lsets[b], tile=128)
            err = float(jnp.max(jnp.abs(f.beta - bf.beta[b])) /
                        (jnp.max(jnp.abs(f.beta)) + 1e-30))
            assert err < 1e-4, (b, err)

        mesh2 = mesh_lib.make_local_mesh_2d(model_parallelism=2)
        with sharding.activate(mesh2):
            bf2 = nystrom.fit_streaming_batched(kern, x, ys, lams, lsets,
                                                tile=128)
        err = float(jnp.max(jnp.abs(bf2.beta - bf.beta)) /
                    jnp.max(jnp.abs(bf.beta)))
        assert err < 1e-3, err   # psum order through the fp32 solve

        xq = jnp.asarray(rng.normal(size=(256, d)), jnp.float32)
        pred = nystrom.predict_streaming_batched(kern, bf, xq, tile=64)
        assert pred.shape == (B, 256), pred.shape
        for b in range(B):
            f = nystrom.NystromFit(beta=bf.beta[b],
                                   landmarks=bf.landmarks[b],
                                   landmark_idx=bf.landmark_idx[b],
                                   lam=float(lams[b]))
            p = nystrom.predict_streaming(kern, f, xq, tile=64)
            np.testing.assert_allclose(np.asarray(p), np.asarray(pred[b]),
                                       rtol=1e-5, atol=1e-5)
        with sharding.activate(mesh2):
            pred2 = nystrom.predict_streaming_batched(kern, bf, xq, tile=64)
        np.testing.assert_allclose(np.asarray(pred2), np.asarray(pred),
                                   rtol=1e-5, atol=1e-5)

        # weights + shared-y broadcast variants
        w = jnp.asarray(rng.uniform(0.5, 2.0, size=(B, m)), jnp.float32)
        bf4 = nystrom.fit_streaming_batched(kern, x, ys, lams, lsets,
                                            tile=128, weights=w)
        f1 = nystrom.fit_streaming(kern, x, ys[2], float(lams[2]), lsets[2],
                                   tile=128, weights=w[2])
        err = float(jnp.max(jnp.abs(bf4.beta[2] - f1.beta)) /
                    (jnp.max(jnp.abs(f1.beta)) + 1e-30))
        assert err < 1e-4, err
        print("BATCHED2D_OK")
    """
    _run_forced(body, "BATCHED2D_OK")


def test_mesh_construction_validation():
    """Production/local mesh factories: shape derivation + divisibility
    errors (no forced devices needed for the error paths)."""
    body = """
        import jax, pytest
        from repro.launch import mesh as mesh_lib

        assert jax.device_count() == 4
        m = mesh_lib.make_production_mesh(model_parallelism=2)
        assert m.shape == {"data": 2, "model": 2}
        m = mesh_lib.make_production_mesh(model_parallelism=2,
                                          num_devices=2)
        assert m.shape == {"data": 1, "model": 2}
        m2 = mesh_lib.make_local_mesh_2d(model_parallelism=2)
        assert m2.axis_names == ("data", "model")
        try:
            mesh_lib.make_production_mesh(model_parallelism=3)
        except ValueError as e:
            assert "not divisible" in str(e)
        else:
            raise AssertionError("expected ValueError")
        try:
            mesh_lib.make_local_mesh_2d(model_parallelism=3)
        except ValueError as e:
            assert "divisor" in str(e)
        else:
            raise AssertionError("expected ValueError")
        print("MESHVAL_OK")
    """
    _run_forced(body, "MESHVAL_OK")
