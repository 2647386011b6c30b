"""Binned-KDE deposit subsystem: Pallas kernel (interpret) vs windowed XLA
scatter vs corner-loop oracle, density parity vs kde_direct, and
sharded-vs-single-device grid parity on a forced 2-device mesh."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kde
from repro.kernels import dispatch
from repro.kernels.kde_binned import kernel as kb_kernel
from repro.kernels.kde_binned import ops as kb_ops
from repro.kernels.kde_binned import ref as kb_ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _setup(d: int, g: int, n: int = 600, seed: int = 0):
    x = jax.random.uniform(jax.random.PRNGKey(seed), (n, d)) * 2.0 - 0.5
    lo = jnp.full((d,), -0.7)
    spacing = (jnp.full((d,), 1.7) - lo) / (g - 1)
    return x, lo, spacing


# ----------------------------------------------------------- scatter parity --
@pytest.mark.parametrize("d,g", [(1, 64), (2, 48), (3, 24)])
def test_scatter_pallas_matches_ref(d, g):
    x, lo, spacing = _setup(d, g)
    want = kb_ref.binned_grid(x, lo, spacing, g)
    got = kb_ops.binned_scatter(x, lo, spacing, g, bm=64, interpret=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)
    # total deposited mass is exactly n (partition-of-unity stencil)
    assert float(jnp.sum(got)) == pytest.approx(x.shape[0], rel=1e-5)


@pytest.mark.parametrize("d,g", [(1, 64), (2, 48), (3, 24)])
@pytest.mark.parametrize("tile", [None, 100])
def test_scatter_windowed_xla_matches_ref(d, g, tile):
    x, lo, spacing = _setup(d, g, seed=1)
    want = kb_ref.binned_grid(x, lo, spacing, g)
    got = kde.scatter_cic(x, lo, spacing, g, tile=tile)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)


def test_scatter_weighted_and_dispatch_routes():
    d, g = 2, 32
    x, lo, spacing = _setup(d, g, n=300, seed=2)
    w = jax.random.uniform(jax.random.PRNGKey(3), (300,)) + 0.5
    want = kb_ref.binned_grid(x, lo, spacing, g, weights=w)
    for backend, kw in [("xla", dict(tile=64)),
                        ("pallas", dict(interpret=True))]:
        got = dispatch.binned_scatter(x, lo, spacing, g, backend=backend,
                                      weights=w, **kw)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6,
                                   err_msg=backend)


def test_scatter_matches_corner_loop_oracle_tight():
    """The windowed deposit must reproduce the pre-refactor corner-loop
    numbers (the acceptance bar for the KDE front-end swap) at rtol 1e-5."""
    d, g = 3, 24
    x, lo, spacing = _setup(d, g, n=900, seed=4)
    old = kb_ref.binned_grid(x, lo, spacing, g)
    new = kde.scatter_cic(x, lo, spacing, g, tile=256)
    np.testing.assert_allclose(new, old, rtol=1e-5, atol=1e-7)


# ------------------------------------------------------- sorted deposits --
@pytest.mark.parametrize("d,g", [(1, 64), (2, 48), (3, 24)])
def test_scatter_segment_method_matches_window(d, g):
    """`scatter_cic(method="segment")` (sort + segment_sum) matches the
    historical windowed deposit at rtol 1e-5 — with and without weights,
    tiled and one-shot."""
    x, lo, spacing = _setup(d, g, n=700, seed=5)
    w = jax.random.uniform(jax.random.PRNGKey(6), (700,)) + 0.5
    for weights in (None, w):
        for tile in (None, 128):
            want = kde.scatter_cic(x, lo, spacing, g, weights=weights,
                                   tile=tile)
            got = kde.scatter_cic(x, lo, spacing, g, weights=weights,
                                  tile=tile, method="segment")
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


def test_scatter_duplicate_cell_collisions():
    """Hundreds of points stacked into a handful of cells: the sorted
    deposits must sum every colliding corner (the regime the old
    serial scatter handled by construction, and a vectorized deposit can
    silently drop)."""
    d, g, n = 3, 24, 500
    lo = jnp.full((d,), -0.7)
    spacing = (jnp.full((d,), 1.7) - lo) / (g - 1)
    # all points land in 4 distinct cells, jittered inside each cell
    cells = jax.random.randint(jax.random.PRNGKey(7), (4, d), 2, g - 3)
    pick = jax.random.randint(jax.random.PRNGKey(8), (n,), 0, 4)
    jit_ = jax.random.uniform(jax.random.PRNGKey(9), (n, d))
    x = lo + (cells[pick] + jit_) * spacing
    want = kb_ref.binned_grid(x, lo, spacing, g)
    got = kb_ops.binned_scatter(x, lo, spacing, g, bm=64, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    seg = kde.scatter_cic(x, lo, spacing, g, method="segment")
    np.testing.assert_allclose(seg, want, rtol=1e-5, atol=1e-6)
    assert float(jnp.sum(got)) == pytest.approx(n, rel=1e-5)


def _stacked_points(d: int, g: int, n: int = 1000):
    """n points piled into 6 cells (long equal-row runs in every chunk);
    n is not a multiple of the 64-row tile, so the last chunk ends in a
    ragged tail of zero-weight pads."""
    lo = jnp.full((d,), -0.7)
    spacing = (jnp.full((d,), 1.7) - lo) / (g - 1)
    cells = jax.random.randint(jax.random.PRNGKey(30 + d), (6, d), 2, g - 3)
    pick = jax.random.randint(jax.random.PRNGKey(40 + d), (n,), 0, 6)
    jit_ = jax.random.uniform(jax.random.PRNGKey(50 + d), (n, d))
    return lo + (cells[pick] + jit_) * spacing, lo, spacing


def _argsort_stream(stream):
    """Reference prep: argsort the cells, gather every array."""
    order = jnp.argsort(stream[0], stable=True)
    return tuple(a[order] for a in stream)


@pytest.mark.parametrize("d,g", [(1, 64), (2, 48), (3, 24)])
def test_sorted_corner_stream_matches_argsort_gathers(d, g):
    """The keyed sort's stream is bitwise the argsort-and-gather stream:
    cells, fractions and weights; the chunked layout pads the ragged tail
    with zero-weight points on the last real row."""
    x, lo, spacing = _stacked_points(d, g)
    w = jax.random.uniform(jax.random.PRNGKey(60 + d), (x.shape[0],)) + 0.5
    stream = kb_ops.point_stream(x, lo, spacing, g, weights=w)
    assert len(stream) == 2 + d
    got = kb_ops.sort_stream(stream)
    want = _argsort_stream(stream)
    for k, (a, b) in enumerate(zip(got, want)):
        assert a.dtype == b.dtype, k
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(k))
    first, last, rb, fw = kb_ops.chunk_stream(got, d, g, bm=64)
    assert rb.shape == (16, 2, 64) and fw.shape == (16, 1 + 2 ** (d - 1), 64)
    rows = np.asarray(rb[:, 0]).ravel()
    assert (np.diff(rows) >= 0).all()            # sorted across chunks
    assert rows[-24] == rows[-1] == int(got[0][-1]) // g
    assert np.asarray(fw[-1, :, -24:] == 0).all()
    np.testing.assert_array_equal(np.asarray(first), np.asarray(rb[:, 0, 0]))
    np.testing.assert_array_equal(np.asarray(last), np.asarray(rb[:, 0, -1]))


@pytest.mark.parametrize("accumulator", ["plain", "compensated"])
@pytest.mark.parametrize("d,g", [(1, 64), (2, 48), (3, 24)])
def test_deposit_grid_bitwise_equals_argsort_stream(d, g, accumulator):
    """The Pallas deposit of `binned_scatter` is bitwise the grid the
    kernel deposits from the argsort-and-gather stream."""
    x, lo, spacing = _stacked_points(d, g)
    comp = accumulator == "compensated"
    stream = _argsort_stream(kb_ops.point_stream(x, lo, spacing, g))
    out = kb_kernel.scatter_sorted(
        *kb_ops.chunk_stream(stream, d, g, bm=64), dim=d, grid_size=g,
        lanes_dim=g, compensated=comp, interpret=True)
    want = (out[0] + out[1]) if comp else out
    want = np.asarray(want)[:g ** (d - 1)].reshape((g,) * d)
    got = kb_ops.binned_scatter(x, lo, spacing, g, bm=64, interpret=True,
                                accumulator=accumulator)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert float(jnp.sum(got)) == pytest.approx(x.shape[0], rel=1e-5)


# Hand-made streams for the one-hot window reduction, on a unit lattice
# (lo 0, spacing 1: a point's base cell is its integer part).  P = 64 points
# a chunk, W = 128 rows a window (8 at d = 1, where the lattice is one row).
_LATTICE = {1: 64, 2: 300, 3: 24}        # R = 1, 300, 576 rows


def _leading(r0: int, d: int, g: int) -> list[int]:
    return [(r0 // g ** (d - 2 - k)) % g for k in range(d - 1)]


def _hand_stream(kind: str, d: int):
    """(base rows, hand-counted windows) of a hand-made stream.

    dense: 200 points on one base row (4 chunks, the last ragged): one
    window per plane and chunk.  spread: every 64-point chunk puts half its
    points on row r and half on r + 130, so each plane of each chunk spans
    two windows.  distinct: 256 points on 256 distinct rows (at d = 1, the
    lattice's one row), windows counted in plain Python over the chunks.
    """
    g = _LATTICE[d]
    planes = 2 ** (d - 1)
    if d == 1:
        n = 200 if kind == "dense" else 256
        return [0] * n, -(-n // 64)
    if kind == "dense":
        return [127] * 200, 4 * planes
    if kind == "spread":
        starts = (0, 140) if d == 2 else (0, 140, 280, 420)
        rows = [r + 130 * (k >= 32) for r in starts for k in range(64)]
        return rows, 2 * planes * len(starts)
    valid = [r for r in range(g ** (d - 1))
             if all(c <= g - 2 for c in _leading(r, d, g))]
    rows = valid[::2][:256] if d == 3 else valid[:256]
    offsets = kb_kernel.plane_offsets(d, g)
    count = 0
    for c in range(0, len(rows), 64):
        chunk = rows[c:c + 64]
        for off in offsets:
            count += (chunk[-1] + off) // 128 - (chunk[0] + off) // 128 + 1
    return rows, count


@pytest.mark.parametrize("kind", ["dense", "spread", "distinct"])
@pytest.mark.parametrize("accumulator", ["plain", "compensated"])
@pytest.mark.parametrize("d", [1, 2, 3])
def test_deposit_matches_oracle_on_hand_made_streams(d, accumulator, kind):
    """The one-hot window deposit matches the corner-loop oracle at rtol
    1e-5 on a dense, a window-spanning and a distinct-row stream; the
    window counts that bound the kernel's loops match a hand count and
    stay within 2^(d-1) (n_chunks + Rp / W)."""
    g = _LATTICE[d]
    rows, hand = _hand_stream(kind, d)
    n = len(rows)
    key = jax.random.PRNGKey(70 + d)
    last = jax.random.randint(key, (n,), 0, g - 1)
    lead = jnp.asarray([_leading(r, d, g) for r in rows],
                       jnp.int32).reshape(n, d - 1)
    base = jnp.concatenate([lead, last[:, None]], axis=1)
    frac = jax.random.uniform(jax.random.PRNGKey(80 + d), (n, d),
                              minval=0.05, maxval=0.95)
    x = base.astype(jnp.float32) + frac
    lo, spacing = jnp.zeros((d,)), jnp.ones((d,))
    want = kb_ref.binned_grid(x, lo, spacing, g)
    got = kb_ops.binned_scatter(x, lo, spacing, g, bm=64, interpret=True,
                                accumulator=accumulator)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
    first, last_row, _, _ = kb_ops.chunk_stream(
        kb_ops.sort_stream(kb_ops.point_stream(x, lo, spacing, g)), d, g,
        bm=64)
    counts = np.asarray(kb_ops.window_counts(first, last_row, d, g))
    assert int(counts.sum()) == hand, (counts, hand)
    win = kb_kernel.window_rows(g ** (d - 1))
    rows_pad = -(-g ** (d - 1) // win) * win
    assert counts.sum() <= 2 ** (d - 1) * (len(counts) + rows_pad // win)


def test_scatter_pallas_compensated_state_and_parity():
    """Compensated Pallas deposit: the (hi, lo) state folds to the plain
    grid, and finalize=False returns the un-collapsed pair (the form a mesh
    psum would cross)."""
    d, g = 3, 24
    x, lo, spacing = _setup(d, g, n=900, seed=10)
    plain = kb_ops.binned_scatter(x, lo, spacing, g, bm=64, interpret=True)
    comp = kb_ops.binned_scatter(x, lo, spacing, g, bm=64, interpret=True,
                                 accumulator="compensated")
    np.testing.assert_allclose(comp, plain, rtol=1e-5, atol=1e-7)
    hi, lo_bank = kb_ops.binned_scatter(x, lo, spacing, g, bm=64,
                                        interpret=True,
                                        accumulator="compensated",
                                        finalize=False)
    assert hi.shape == lo_bank.shape == (g,) * d
    np.testing.assert_array_equal(np.asarray(hi + lo_bank), np.asarray(comp))


def test_dispatch_compensated_deposit_stays_on_pallas(monkeypatch):
    """`dispatch.binned_scatter(backend="pallas", accumulator="compensated")`
    must run the Pallas deposit kernel — the historical silent
    reroute to the XLA scatter is gone."""
    from repro.core import kde as core_kde

    def boom(*a, **k):
        raise AssertionError("compensated deposit rerouted to XLA")

    monkeypatch.setattr(core_kde, "scatter_cic", boom)
    d, g = 2, 32
    x, lo, spacing = _setup(d, g, n=400, seed=11)
    want = kb_ref.binned_grid(x, lo, spacing, g)
    got = dispatch.binned_scatter(x, lo, spacing, g, backend="pallas",
                                  interpret=True,
                                  accumulator="compensated")
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


# ----------------------------------------------------------- density parity --
@pytest.mark.parametrize("d", [1, 2, 3])
def test_kde_binned_backends_agree_and_track_direct(d):
    """Pallas (interpret) vs XLA deposits give the same density, and both
    stay within binning error of the exact kde_direct oracle."""
    n, g, h = 1200, 96, 0.1
    x = jax.random.uniform(jax.random.PRNGKey(10 + d), (n, d))
    via_xla = np.asarray(kde.kde_binned(x, x, h, grid_size=g, backend="xla",
                                        tile=256))
    via_pallas = np.asarray(kde.kde_binned(x, x, h, grid_size=g,
                                           backend="pallas", interpret=True))
    np.testing.assert_allclose(via_pallas, via_xla, rtol=1e-5, atol=1e-9)
    direct = np.asarray(kde.kde_direct(x, x, h))
    rel = np.abs(via_xla / direct - 1.0)
    assert np.median(rel) < 0.02, np.median(rel)


def test_estimate_densities_streaming_tile_invariance():
    """The deposit tile is an execution detail: densities must not depend
    on it beyond fp32 reduction order."""
    x = jax.random.uniform(jax.random.PRNGKey(20), (2048, 3))
    a = np.asarray(kde.estimate_densities(x, tile=None))
    b = np.asarray(kde.estimate_densities(x, tile=500))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-9)


# ------------------------------------------------------------ sharded parity --
def test_sharded_kde_grid_matches_single_device():
    """kde_binned_sharded on a forced 2-device mesh == single-device
    kde_binned on the same bounds (up to psum reduction order)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.core import distributed as D
        from repro.core import kde
        from repro.distributed import sharding as shd
        from repro.launch import mesh as mesh_lib
        assert jax.device_count() == 2
        n, d, g = 2048, 3, 48
        x = jax.random.uniform(jax.random.PRNGKey(0), (n, d))
        h = jnp.asarray(kde.scott_bandwidth(x), x.dtype)
        lo, hi = kde.binned_bounds(x, x, h)
        ref = kde.kde_binned(x, x, h, grid_size=g)
        mesh = mesh_lib.make_local_mesh(devices=jax.devices()[:2])
        with shd.activate(mesh):
            sh = D.kde_binned_sharded(x, h, grid_size=g, lo=lo, hi=hi,
                                      tile=512)
        np.testing.assert_allclose(np.asarray(sh), np.asarray(ref),
                                   rtol=2e-5, atol=1e-9)
        print("KDE_SHARDED_OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"), XLA_FLAGS="")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "KDE_SHARDED_OK" in out.stdout
