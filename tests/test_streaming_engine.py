"""Unified streaming tile-reduction engine (`repro.core.streaming`).

Locks the refactor's three contracts:

  * plain-mode BIT-parity: the engine-backed public functions
    (`nystrom.scan_normal_eq` / `fit_streaming` (weighted + multi-lam) /
    `predict_streaming[_multi]`, `kde.scatter_cic`) reproduce the
    pre-refactor hand-rolled loops bit-for-bit — the reference
    implementations are copied verbatim below from the PR-4 sources;
  * compensated accuracy: on an n >= 1e5 stream the two-float fp32 Gram
    matches the f64 accumulation of the SAME f32 kernel tiles (the
    quantity the accumulator knob owns) at least 10x more tightly than
    plain fp32, and `solve_normal_eq`'s lowered noise floor retains
    whitened directions plain fp32 truncates (beta recovers the f64
    solution on an adversarially ill-conditioned landmark set);
  * mesh transport: the compensated (hi, lo) pair survives the psum on a
    forced 2-device host mesh (subprocess).
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

from repro.core import kde, kernels as K, nystrom, streaming
from repro.core.kernels import kernel_matrix, pad_rows_sentinel, round_up
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


# ------------------------------------------------- pre-refactor references --
# Copied VERBATIM from the PR-4 bodies of nystrom.scan_normal_eq,
# kde.scatter_cic and nystrom.predict_streaming's local slab loop.  These are
# the bit-parity oracles for the engine's "plain" mode.

def _scan_normal_eq_ref(kernel, x, xm, w, *, tile=8192):
    n, d = x.shape
    m = xm.shape[0]
    acc = jnp.promote_types(x.dtype, jnp.float32)
    tile = min(tile, n)
    np_ = round_up(n, tile)
    xt = pad_rows_sentinel(x, np_).reshape(np_ // tile, tile, d)
    wt = jnp.pad(w.astype(acc), (0, np_ - n)).reshape(np_ // tile, tile)

    def step(carry, xw):
        g, r = carry
        xi, wi = xw
        k = kernel_matrix(kernel, xi, xm).astype(acc)
        g = g + jax.lax.dot_general(k, k, (((0,), (0,)), ((), ())),
                                    preferred_element_type=acc)
        r = r + jax.lax.dot_general(k, wi, (((0,), (0,)), ((), ())),
                                    preferred_element_type=acc)
        return (g, r), None

    init = (jnp.zeros((m, m), acc), jnp.zeros((m,), acc))
    (g, r), _ = jax.lax.scan(step, init, (xt, wt))
    return g, r


def _scatter_cic_ref(points, lo, spacing, grid_size, weights=None, tile=None):
    import functools

    @functools.partial(jax.jit, static_argnames=("grid_size", "tile"))
    def impl(points, lo, spacing, grid_size, weights=None, tile=None):
        n, d = points.shape
        dnums = jax.lax.ScatterDimensionNumbers(
            update_window_dims=tuple(range(1, d + 1)),
            inserted_window_dims=(),
            scatter_dims_to_operand_dims=tuple(range(d)))

        def deposit(grid, pts, w):
            base, frac = kde.cic_prep(pts, lo, spacing, grid_size)
            return jax.lax.scatter_add(grid, base, kde._cic_stencil(frac, w),
                                       dnums)

        grid0 = jnp.zeros((grid_size,) * d, dtype=points.dtype)
        if tile is None or tile >= n:
            return deposit(grid0, points, weights)
        np_ = round_up(n, tile)
        w = jnp.ones((n,), points.dtype) if weights is None else weights
        pts = jnp.pad(points, ((0, np_ - n), (0, 0))).reshape(-1, tile, d)
        wt = jnp.pad(w, (0, np_ - n)).reshape(-1, tile)

        def step(grid, pw):
            return deposit(grid, pw[0], pw[1]), None

        grid, _ = jax.lax.scan(step, grid0, (pts, wt))
        return grid

    return impl(points, lo, spacing, grid_size, weights=weights, tile=tile)


def _predict_ref(kernel, fit_, x_new, tile):
    from repro.kernels import dispatch
    n, d = x_new.shape
    xm, beta = fit_.landmarks, fit_.beta
    t = min(tile, n)
    np_ = round_up(n, t)
    tiles = pad_rows_sentinel(x_new, np_).reshape(np_ // t, t, d)

    def one(xt):
        return dispatch.kernel_matrix(kernel, xt, xm) @ beta

    return jax.lax.map(one, tiles).reshape(np_)[:n]


# -------------------------------------------------------- plain bit-parity --

@pytest.mark.parametrize("n,tile", [(1000, 192), (2048, 512), (300, 8192)])
def test_scan_normal_eq_bit_parity(n, tile):
    """Engine-tiled Gram accumulation == the pre-refactor lax.scan, bitwise
    (ragged tail, one-tile and multi-tile shapes)."""
    kx, ky, kw = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(kx, (n, 3))
    w = jax.random.normal(kw, (n,))
    xm = x[jax.random.permutation(ky, n)[:48]]
    g0, r0 = _scan_normal_eq_ref(KERN, x, xm, w, tile=tile)
    g1, r1 = nystrom.scan_normal_eq(KERN, x, xm, w, tile=tile)
    np.testing.assert_array_equal(np.asarray(g0), np.asarray(g1))
    np.testing.assert_array_equal(np.asarray(r0), np.asarray(r1))


@pytest.mark.parametrize("weighted", [False, True])
def test_fit_streaming_bit_parity(weighted):
    """fit_streaming (plain) == reference Gram -> k_mm -> solve pipeline,
    bitwise, weighted and unweighted."""
    data = krr_data.bimodal(jax.random.PRNGKey(0), 2048, d=3)
    idx = jnp.arange(0, 2048, 32)[:64]
    lam = 1e-3
    w = (1.0 + jnp.arange(64, dtype=jnp.float32) / 16.0) if weighted else None
    xm = jnp.take(data.x, idx, axis=0)
    g, rhs = _scan_normal_eq_ref(KERN, data.x, xm, data.y, tile=512)
    k_mm = kernel_matrix(KERN, xm).astype(g.dtype)
    if w is not None:
        g, rhs, k_mm = nystrom.weighted_normal_eq(g, rhs, k_mm, w)
    beta_ref = nystrom.solve_normal_eq(g, rhs, k_mm, 2048, lam)
    if w is not None:
        beta_ref = w * beta_ref
    fit = nystrom.fit_streaming(KERN, data.x, data.y, lam, idx, tile=512,
                                weights=w)
    np.testing.assert_array_equal(np.asarray(fit.beta), np.asarray(beta_ref))


def test_fit_streaming_multi_bit_parity():
    """The multi-lam sweep rides the same engine stream: every fit bitwise
    equals the reference composition."""
    data = krr_data.bimodal(jax.random.PRNGKey(1), 2048, d=3)
    idx = jnp.arange(0, 2048, 32)[:64]
    lams = [1e-2, 1e-3, 1e-4]
    xm = jnp.take(data.x, idx, axis=0)
    g, rhs = _scan_normal_eq_ref(KERN, data.x, xm, data.y, tile=512)
    k_mm = kernel_matrix(KERN, xm).astype(g.dtype)
    state = nystrom.normal_eq_state(KERN, data.x, data.y, idx, tile=512)
    grid = nystrom.solve_from_state(state, lams)
    for lam, beta in zip(lams, grid.beta):
        want = nystrom.solve_normal_eq(g, rhs, k_mm, 2048, lam)
        np.testing.assert_array_equal(np.asarray(beta), np.asarray(want))


@pytest.mark.parametrize("tile", [100, 333, 4096])
def test_predict_streaming_bit_parity(tile):
    data = krr_data.bimodal(jax.random.PRNGKey(0), 2048, d=3)
    idx = jnp.arange(0, 2048, 32)[:64]
    fit = nystrom.fit_streaming(KERN, data.x, data.y, 1e-3, idx, tile=512)
    want = _predict_ref(KERN, fit, data.x[:777], tile)
    got = nystrom.predict_streaming(KERN, fit, data.x[:777], tile=tile)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_predict_streaming_multi_bit_parity():
    """The multi-beta predict shares the reference's tile stream: with a
    single fit it must reproduce predict_streaming bitwise, modulo the
    (1, n) stacking."""
    data = krr_data.bimodal(jax.random.PRNGKey(3), 1024, d=3)
    idx = jnp.arange(0, 1024, 16)[:48]
    state = nystrom.normal_eq_state(KERN, data.x, data.y, idx, tile=256)
    grid = nystrom.solve_from_state(state, [1e-3])
    multi = nystrom.predict_streaming(KERN, grid._replace(beta=grid.beta.T),
                                      data.x[:300], tile=128).T
    assert multi.shape == (1, 300)


@pytest.mark.parametrize("tile,weighted", [(None, False), (100, False),
                                           (100, True), (None, True)])
def test_scatter_cic_bit_parity(tile, weighted):
    x = jax.random.uniform(jax.random.PRNGKey(1), (601, 3)) * 2.0 - 0.5
    lo = jnp.full((3,), -0.7)
    spacing = (jnp.full((3,), 1.7) - lo) / 23
    w = (jax.random.uniform(jax.random.PRNGKey(5), (601,)) + 0.5
         if weighted else None)
    want = _scatter_cic_ref(x, lo, spacing, 24, weights=w, tile=tile)
    got = kde.scatter_cic(x, lo, spacing, 24, weights=w, tile=tile)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------- compensated accuracy --

def test_two_sum_exact():
    """two_sum is an error-free transformation: hi + lo == the exact sum in
    f64 for adversarial magnitude gaps that plain f32 rounds away."""
    a = jnp.asarray([1e8, 1.0, -1e8, 3.25e-4], jnp.float32)
    b = jnp.asarray([3.25e-4, 1e8, 1.0, 1e8], jnp.float32)
    s, e = streaming.two_sum(a, b)
    exact = np.asarray(a, np.float64) + np.asarray(b, np.float64)
    np.testing.assert_array_equal(
        np.asarray(s, np.float64) + np.asarray(e, np.float64), exact)


def test_tile_reduce_compensated_beats_plain_on_adversarial_stream():
    """Summing an adversarial (large-offset) stream: the compensated engine
    matches the f64 sum to ~ulp while plain f32 drifts with the tile count."""
    n, tile = 131072, 64
    vals = (jnp.ones((n,), jnp.float32) * 0.1
            + jnp.where(jnp.arange(n) % 977 == 0, 1.0e5, 0.0))
    init = jnp.zeros((), jnp.float32)
    emit = lambda v: jnp.sum(v)

    plain = streaming.tile_reduce(emit, vals, tile=tile, init=init)
    comp = streaming.tile_reduce(emit, vals, tile=tile, init=init,
                                 accumulator="compensated")
    exact = float(np.sum(np.asarray(vals, np.float64)))
    err_plain = abs(float(plain) - exact)
    err_comp = abs(float(comp) - exact)
    assert err_comp * 10 < err_plain, (err_plain, err_comp)


def test_compensated_gram_10x_tighter_than_plain():
    """Acceptance bar: at n >= 1e5, the compensated fp32 streaming Gram
    matches the f64 accumulation of the SAME f32 kernel tiles (the
    quantity the accumulator owns — kernel-tile rounding is identical in
    all three paths) at least 10x more tightly than plain fp32."""
    n, m, d, tile = 131072, 64, 3, 256
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d), dtype=jnp.float32)
    xm = x[:m]
    gp, _ = nystrom.scan_normal_eq(KERN, x, xm, jnp.zeros((n,)), tile=tile)
    (gh, _), (gl, _) = nystrom.scan_normal_eq(
        KERN, x, xm, jnp.zeros((n,)), tile=tile, accumulator="compensated",
        finalize=False)
    # f64 accumulation of the same f32 tiles (host side)
    tiles = jax.jit(lambda xt: kernel_matrix(KERN, xt, xm))
    ref = np.zeros((m, m), np.float64)
    for i in range(n // tile):
        k = np.asarray(tiles(x[i * tile:(i + 1) * tile]), np.float64)
        ref += k.T @ k
    scale = np.abs(ref).max()
    err_plain = np.abs(np.asarray(gp, np.float64) - ref).max() / scale
    err_comp = np.abs(np.asarray(gh, np.float64)
                      + np.asarray(gl, np.float64) - ref).max() / scale
    assert err_comp * 10 <= err_plain, (err_plain, err_comp)


def test_compensated_solve_retains_truncated_directions():
    """Regression for the ROADMAP scale-ceiling item: on an adversarially
    ill-conditioned landmark set (near-duplicate clusters -> tiny K_mm
    eigenvalues) with small lam, the plain fp32 solve truncates whitened
    directions at its noise floor and loses the f64 solution; the
    compensated stream + lowered floor (streaming.EPS_SCALE) keeps them and
    recovers it.  f64 reference in a subprocess (enable_x64)."""
    out = run_sub("""
        from repro.core import kernels as K, nystrom, streaming
        kern = K.Matern(nu=1.5)
        n, m, d, tile = 65536, 48, 3, 512
        x = jax.random.normal(jax.random.PRNGKey(0), (n, d),
                              dtype=jnp.float32)
        base = x[:m // 2]
        dup = base + 3e-4 * jax.random.normal(jax.random.PRNGKey(1),
                                              base.shape, dtype=jnp.float32)
        xm = jnp.concatenate([base, dup])
        y = jnp.sin(3 * x[:, 0]) + 0.1 * jax.random.normal(
            jax.random.PRNGKey(2), (n,), dtype=jnp.float32)
        lam = 1e-6

        def solve(accumulator):
            g, r = nystrom.streaming_normal_eq(kern, x, y, xm, tile=tile,
                                               accumulator=accumulator)
            k_mm = K.kernel_matrix(kern, xm).astype(g.dtype)
            return nystrom.solve_normal_eq(
                g, r, k_mm, n, lam,
                eps_scale=streaming.eps_scale(accumulator))

        beta_p = solve("plain")
        beta_c = solve("compensated")

        # f64 reference: same stream in f64 end to end
        x64, xm64 = x.astype(jnp.float64), xm.astype(jnp.float64)
        g64 = jnp.zeros((m, m), jnp.float64)
        r64 = jnp.zeros((m,), jnp.float64)
        for i in range(0, n, 8192):
            k = K.kernel_matrix(kern, x64[i:i + 8192], xm64)
            g64 = g64 + k.T @ k
            r64 = r64 + k.T @ y[i:i + 8192].astype(jnp.float64)
        k_mm64 = K.kernel_matrix(kern, xm64)
        beta64 = nystrom.solve_normal_eq(g64, r64, k_mm64, n, lam)

        ep = float(jnp.linalg.norm(beta_p.astype(jnp.float64) - beta64)
                   / jnp.linalg.norm(beta64))
        ec = float(jnp.linalg.norm(beta_c.astype(jnp.float64) - beta64)
                   / jnp.linalg.norm(beta64))
        # plain truncates the near-duplicate directions entirely (O(1)
        # error); compensated + lowered floor recovers the f64 solution
        assert ep > 0.1, ep
        assert ec < 1e-3, ec
        assert ec * 50 < ep, (ep, ec)
        print("RETAIN_OK", ep, ec)
    """, env_extra={"JAX_ENABLE_X64": "1"})
    assert "RETAIN_OK" in out


def test_pallas_compensated_gram_matches_engine_scan():
    """The two-float VMEM accumulator inside the Pallas gram body computes
    the same compensated sum as the XLA engine scan at equal tile
    granularity (bm == tile == 256 -> identical fold order), with a live
    error channel (nonzero lo) once several row tiles stream through."""
    from repro.kernels.gram import ops as gram_ops
    n, m = 4096, 64
    x = jax.random.normal(jax.random.PRNGKey(0), (n, 3))
    w = jax.random.normal(jax.random.PRNGKey(1), (n,))
    xm = x[:m]
    (gh, rh), (gl, rl) = gram_ops.gram_matrix(
        KERN, x, xm, w, interpret=True, accumulator="compensated",
        finalize=False)
    assert float(jnp.abs(gl).max()) > 0.0
    g_scan, r_scan = nystrom.scan_normal_eq(KERN, x, xm, w, tile=256,
                                            accumulator="compensated")
    np.testing.assert_array_equal(np.asarray(gh + gl), np.asarray(g_scan))
    np.testing.assert_array_equal(np.asarray(rh + rl), np.asarray(r_scan))


# ------------------------------------------------------------ mesh transport --

@pytest.mark.slow
def test_compensated_pair_survives_psum():
    """Forced 2-device mesh: the (hi, lo) state crosses the Gram psum
    un-collapsed — lo is psum-reduced alongside hi and the finalized sharded
    result stays within reduction-order noise of the single-device
    compensated result (and carries a genuinely nonzero lo)."""
    out = run_sub("""
        from repro.core import kernels as K, nystrom, streaming
        from repro.distributed import sharding as shd
        from repro.launch import mesh as mesh_lib
        assert jax.device_count() == 2, jax.devices()
        kern = K.Matern(nu=1.5)
        n, m = 32768, 48
        x = jax.random.normal(jax.random.PRNGKey(0), (n, 3))
        y = jax.random.normal(jax.random.PRNGKey(1), (n,))
        xm = x[:m]
        g_ref, r_ref = nystrom.streaming_normal_eq(
            kern, x, y, xm, tile=512, accumulator="compensated")
        mesh = mesh_lib.make_local_mesh(devices=jax.devices()[:2])
        with shd.activate(mesh):
            state = nystrom.streaming_normal_eq(
                kern, x, y, xm, tile=512, accumulator="compensated",
                finalize=False)
            (g_hi, r_hi), (g_lo, r_lo) = state
            g_sh, r_sh = streaming.get("compensated").finalize(state)
        # the error channel is alive after the collective
        assert float(jnp.abs(g_lo).max()) > 0.0
        # and the pair equals the single-device compensated accumulation up
        # to the reduction-order change of splitting the stream in two
        np.testing.assert_allclose(np.asarray(g_sh), np.asarray(g_ref),
                                   rtol=2e-6, atol=1e-4)
        np.testing.assert_allclose(np.asarray(r_sh), np.asarray(r_ref),
                                   rtol=2e-5, atol=1e-4)

        # one tile PER CHIP: each chip's lo is exactly zero and the
        # adaptive floor must keep compensated == plain bit-for-bit (the
        # steps budget counts per-chip scan steps, not global n / tile)
        n2 = 16384
        idx = jnp.arange(0, n2, n2 // m)[:m]
        with shd.activate(mesh):
            fp = nystrom.fit_streaming(kern, x[:n2], y[:n2], 1e-4, idx,
                                       tile=8192)
            fc = nystrom.fit_streaming(kern, x[:n2], y[:n2], 1e-4, idx,
                                       tile=8192, accumulator="compensated")
        np.testing.assert_array_equal(np.asarray(fp.beta),
                                      np.asarray(fc.beta))
        print("PSUM_PAIR_OK")
    """, env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert "PSUM_PAIR_OK" in out


# ----------------------------------------------------------- loo threshold --

def test_loo_threshold_matches_race_on_selected_items():
    """The exact leave-one-out threshold coincides with the shared race
    threshold for every SELECTED item (the order-statistics identity that
    makes the historical estimator exact); only the clip-free tail differs."""
    q = jnp.asarray(np.random.default_rng(0).dirichlet(np.full(40, 2.0)),
                    jnp.float32)
    from repro.core import sampling
    i1, w1 = sampling.sample_weighted_without_replacement(
        jax.random.PRNGKey(7), q, 8)
    i2, w2 = sampling.sample_weighted_without_replacement(
        jax.random.PRNGKey(7), q, 8, threshold="loo")
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), rtol=1e-6)
