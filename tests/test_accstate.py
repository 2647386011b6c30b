"""First-class accumulator state (repro.core.accstate): the monoid
contract and its bit-parity guarantees.

Locked here:

  * absorbing a stream in ANY tile-aligned partition reproduces the
    one-shot fold BIT-FOR-BIT (the scan carry continues across absorbs —
    for the plain AND the compensated strategy, Gram and deposit alike);
  * merge is bitwise commutative (IEEE add / TwoSum are symmetric) and
    any merge order agrees within the compensated tolerance;
  * decayed absorption matches an oracle reweighted full refit;
  * the raw pair crosses a forced-2-device psum and merges with a
    replicated prior correctly (subprocess, slow).

A hypothesis property version of the partition test runs when hypothesis
is installed; the seeded parametrized version below always runs.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import accstate, kde, kernels as K, nystrom, streaming
from repro.data import krr_data

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KERN = K.Matern(nu=1.5)
N, D, M, TILE = 2048, 2, 48, 256
LAM = 1e-4


def _data(seed=0, n=N, offset=2.0):
    return krr_data.bimodal(jax.random.PRNGKey(seed), n, D, offset=offset)


def _landmarks(n=N, m=M, seed=3):
    idx = np.random.default_rng(seed).choice(n, m, replace=False)
    return jnp.asarray(np.sort(idx), jnp.int32)


def _partition(seed: int, n: int, tile: int) -> list[tuple[int, int]]:
    """Random tile-aligned partition of [0, n) into >= 2 chunks."""
    rng = np.random.default_rng(seed)
    n_tiles = n // tile
    k = int(rng.integers(1, n_tiles))            # cut points, >= 1
    cuts = np.sort(rng.choice(np.arange(1, n_tiles), size=min(k, n_tiles - 1),
                              replace=False)) * tile
    bounds = np.concatenate([[0], cuts, [n]])
    return list(zip(bounds[:-1], bounds[1:]))


def _absorb_partition(ds, idx, accumulator, parts):
    state = nystrom.normal_eq_init(KERN, ds.x[idx], idx, tile=TILE,
                                   accumulator=accumulator)
    for lo, hi in parts:
        state = nystrom.normal_eq_absorb(KERN, state, ds.x[lo:hi],
                                         ds.y[lo:hi])
    return state


# --------------------------------------------------- partition bit-parity --

@pytest.mark.parametrize("accumulator", ["plain", "compensated"])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_any_tile_aligned_partition_is_bit_equal_to_one_shot(
        accumulator, seed):
    ds = _data()
    idx = _landmarks()
    fit_ref, ref = nystrom.fit_streaming(
        KERN, ds.x, ds.y, LAM, idx, tile=TILE, accumulator=accumulator,
        return_state=True)
    parts = _partition(seed, N, TILE)
    assert len(parts) >= 2
    state = _absorb_partition(ds, idx, accumulator, parts)
    g_ref, rhs_ref = accstate.finalize(ref.acc)
    g, rhs = accstate.finalize(state.acc)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g_ref))
    np.testing.assert_array_equal(np.asarray(rhs).ravel(),
                                  np.asarray(rhs_ref).ravel())
    assert accstate.rows_of(state.acc) == N
    assert accstate.steps_of(state.acc) == accstate.steps_of(ref.acc)
    fit_inc = nystrom.solve_from_state(state, LAM)
    np.testing.assert_array_equal(np.asarray(fit_inc.beta),
                                  np.asarray(fit_ref.beta))


@pytest.mark.parametrize("accumulator", ["plain", "compensated"])
def test_deposit_partition_is_bit_equal_to_one_shot(accumulator):
    ds = _data()
    h = jnp.asarray(0.4, ds.x.dtype)
    lo, hi = kde.binned_bounds(ds.x, ds.x, h)
    grid = 32
    one_shot = kde.scatter_cic(
        ds.x, lo, (hi - lo) / (grid - 1), grid, tile=TILE,
        accumulator=accumulator)
    for seed in (0, 1):
        state = kde.deposit_init(lo, hi, grid, tile=TILE,
                                 accumulator=accumulator)
        for a, b in _partition(seed, N, TILE):
            state = kde.deposit_absorb(state, ds.x[a:b])
        np.testing.assert_array_equal(np.asarray(kde.deposit_finalize(state)),
                                      np.asarray(one_shot))


def test_hypothesis_partition_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    ds = _data()
    idx = _landmarks()
    _, ref = nystrom.fit_streaming(KERN, ds.x, ds.y, LAM, idx, tile=TILE,
                                   return_state=True)
    g_ref, rhs_ref = accstate.finalize(ref.acc)

    @given(seed=st.integers(0, 2**31 - 1))
    @settings(max_examples=10, deadline=None)
    def prop(seed):
        state = _absorb_partition(ds, idx, "plain",
                                  _partition(seed, N, TILE))
        g, rhs = accstate.finalize(state.acc)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(g_ref))
        np.testing.assert_array_equal(np.asarray(rhs).ravel(),
                                      np.asarray(rhs_ref).ravel())

    prop()


# ----------------------------------------------------------------- merge --

@pytest.mark.parametrize("accumulator", ["plain", "compensated"])
def test_merge_is_bitwise_commutative(accumulator):
    ds = _data()
    idx = _landmarks()
    a = _absorb_partition(ds, idx, accumulator, [(0, N // 2)])
    b = _absorb_partition(ds, idx, accumulator, [(N // 2, N)])
    ab = nystrom.normal_eq_merge(a, b)
    ba = nystrom.normal_eq_merge(b, a)
    for la, lb in zip(jax.tree.leaves(ab.acc), jax.tree.leaves(ba.acc)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


@pytest.mark.parametrize("accumulator", ["plain", "compensated"])
def test_merge_any_order_matches_one_shot_within_tolerance(accumulator):
    """Merging independently-built chunk states in any order reproduces the
    one-shot moments to reassociation tolerance; the PREDICTIONS (the
    well-conditioned functional — the whitened solve can amplify last-bit
    G noise through its smallest retained eigenvalues) stay tight."""
    ds = _data()
    idx = _landmarks()
    fit_ref, ref = nystrom.fit_streaming(
        KERN, ds.x, ds.y, LAM, idx, tile=TILE, accumulator=accumulator,
        return_state=True)
    g_ref, rhs_ref = accstate.finalize(ref.acc)
    x_q = ds.x[:64]
    f_ref = np.asarray(nystrom.predict_streaming(KERN, fit_ref, x_q))
    quarters = [(i * N // 4, (i + 1) * N // 4) for i in range(4)]
    states = [_absorb_partition(ds, idx, accumulator, [q]) for q in quarters]
    for order in ((0, 1, 2, 3), (3, 1, 0, 2), (2, 3, 0, 1)):
        merged = states[order[0]]
        for j in order[1:]:
            merged = nystrom.normal_eq_merge(merged, states[j])
        assert accstate.rows_of(merged.acc) == N
        g, rhs = accstate.finalize(merged.acc)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(np.asarray(rhs).ravel(),
                                   np.asarray(rhs_ref).ravel(),
                                   rtol=1e-5, atol=1e-4)
        fit = nystrom.solve_from_state(merged, LAM)
        f = np.asarray(nystrom.predict_streaming(KERN, fit, x_q))
        np.testing.assert_allclose(f, f_ref, rtol=2e-3, atol=2e-3)


def test_merge_rejects_spec_mismatch():
    ds = _data()
    idx = _landmarks()
    a = _absorb_partition(ds, idx, "plain", [(0, N // 2)])
    b = _absorb_partition(ds, idx, "compensated", [(N // 2, N)])
    with pytest.raises(ValueError, match="spec"):
        nystrom.normal_eq_merge(a, b)


# ----------------------------------------------------------------- decay --

@pytest.mark.parametrize("accumulator", ["plain", "compensated"])
def test_decayed_absorb_matches_oracle_reweighted_refit(accumulator):
    """decay(gamma) then absorb == the oracle that refits with every old
    row's contribution scaled by gamma (G and rhs are row-additive)."""
    gamma = 0.75
    ds = _data()
    idx = _landmarks()
    half = N // 2
    state = _absorb_partition(ds, idx, accumulator, [(0, half)])
    state = nystrom.normal_eq_decay(state, gamma)
    state = nystrom.normal_eq_absorb(KERN, state, ds.x[half:], ds.y[half:])
    g, rhs = accstate.finalize(state.acc)

    old = _absorb_partition(ds, idx, accumulator, [(0, half)])
    new = _absorb_partition(ds, idx, accumulator, [(half, N)])
    g_old, rhs_old = accstate.finalize(old.acc)
    g_new, rhs_new = accstate.finalize(new.acc)
    np.testing.assert_allclose(np.asarray(g),
                               gamma * np.asarray(g_old) + np.asarray(g_new),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(rhs).ravel(),
        gamma * np.asarray(rhs_old).ravel() + np.asarray(rhs_new).ravel(),
        rtol=1e-5, atol=1e-5)
    assert np.isclose(accstate.rows_of(state.acc), gamma * half + half)

    # the solve at the decayed state matches the oracle solve on the
    # reweighted moments with the effective sample size — compared through
    # the prediction functional (beta components under the smallest
    # retained eigenvalues amplify last-bit moment noise).  Only the plain
    # floor admits this oracle: the compensated floor sits BELOW the f32
    # finalization noise of the hand-assembled oracle moments, so a
    # near-cutoff eigendirection can flip between the two solves — for
    # compensated the moment-level identity above is the oracle.
    if accumulator == "plain":
        fit = nystrom.solve_from_state(state, LAM)
        n_eff = gamma * half + half
        beta_oracle = nystrom.solve_normal_eq(
            jnp.asarray(gamma * np.asarray(g_old) + np.asarray(g_new)),
            jnp.asarray(gamma * np.asarray(rhs_old).ravel()
                        + np.asarray(rhs_new).ravel()),
            state.k_mm, n_eff, LAM)
        fit_oracle = nystrom.NystromFit(beta=beta_oracle,
                                        landmarks=fit.landmarks,
                                        landmark_idx=fit.landmark_idx,
                                        lam=LAM)
        x_q = ds.x[:64]
        np.testing.assert_allclose(
            np.asarray(nystrom.predict_streaming(KERN, fit, x_q)),
            np.asarray(nystrom.predict_streaming(KERN, fit_oracle, x_q)),
            rtol=2e-3, atol=2e-3)


def test_decay_scales_hi_and_lo_and_preserves_steps():
    ds = _data()
    idx = _landmarks()
    state = _absorb_partition(ds, idx, "compensated", [(0, N)])
    steps0 = accstate.steps_of(state.acc)
    dec = accstate.decay(state.acc, 0.5)
    hi0, lo0 = state.acc.value
    hi1, lo1 = dec.value
    for a, b in zip(jax.tree.leaves(hi0), jax.tree.leaves(hi1)):
        np.testing.assert_array_equal(np.asarray(a) * np.float32(0.5),
                                      np.asarray(b))
    for a, b in zip(jax.tree.leaves(lo0), jax.tree.leaves(lo1)):
        np.testing.assert_array_equal(np.asarray(a) * np.float32(0.5),
                                      np.asarray(b))
    assert accstate.steps_of(dec) == steps0
    assert accstate.rows_of(dec) == pytest.approx(N * 0.5)


# ---------------------------------------------------------------- window --

def test_sliding_window_drops_oldest_chunk_exactly():
    ds = _data()
    idx = _landmarks()
    quarters = [(i * N // 4, (i + 1) * N // 4) for i in range(4)]
    states = [_absorb_partition(ds, idx, "plain", [q]) for q in quarters]
    win = accstate.SlidingWindow(2, merge_fn=nystrom.normal_eq_merge)
    for s in states:
        win.push(s)
    assert len(win) == 2
    folded = win.state()
    oracle = nystrom.normal_eq_merge(states[2], states[3])
    for la, lb in zip(jax.tree.leaves(folded.acc),
                      jax.tree.leaves(oracle.acc)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# --------------------------------------------------------- pytree plumbing --

def test_accstate_roundtrips_as_pytree():
    ds = _data()
    idx = _landmarks()
    state = _absorb_partition(ds, idx, "compensated", [(0, N)])
    leaves, treedef = jax.tree_util.tree_flatten(state)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.acc.spec == state.acc.spec
    assert rebuilt.accumulator == state.accumulator
    fit_a = nystrom.solve_from_state(state, LAM)
    fit_b = nystrom.solve_from_state(rebuilt, LAM)
    np.testing.assert_array_equal(np.asarray(fit_a.beta),
                                  np.asarray(fit_b.beta))


def test_normalize_spec_validates():
    assert accstate.normalize_spec("plain") == "plain"
    assert accstate.normalize_spec(["plain", "compensated"]) == (
        "plain", "compensated")
    with pytest.raises((KeyError, ValueError)):
        accstate.normalize_spec("nope")


# ------------------------------------------------------ forced two-device --

@pytest.mark.slow
def test_merge_with_replicated_prior_across_psum_two_devices():
    """Under a forced 2-device mesh, `mesh_reduce(init_state=...)` must add
    the prior ONCE (merged after the psum) — threading it through each
    chip's local fold would double-count it."""
    code = textwrap.dedent("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.core import accstate, kernels as K, nystrom, streaming
        from repro.distributed import sharding as shd
        from repro.launch import mesh as mesh_lib
        from repro.data import krr_data

        kern = K.Matern(nu=1.5)
        ds = krr_data.bimodal(jax.random.PRNGKey(0), 1024, 2)
        idx = jnp.arange(48, dtype=jnp.int32)
        assert jax.device_count() == 2

        # prior built single-device, then absorbed under the mesh
        prior = nystrom.normal_eq_init(kern, ds.x[idx], idx, tile=128)
        prior = nystrom.normal_eq_absorb(kern, prior, ds.x[:512], ds.y[:512])
        mesh = mesh_lib.make_local_mesh(devices=jax.devices()[:2])
        with shd.activate(mesh):
            state = nystrom.normal_eq_absorb(kern, prior,
                                             ds.x[512:], ds.y[512:])
        g, rhs = accstate.finalize(state.acc)

        ref = nystrom.normal_eq_init(kern, ds.x[idx], idx, tile=128)
        ref = nystrom.normal_eq_absorb(kern, ref, ds.x, ds.y)
        g_ref, rhs_ref = accstate.finalize(ref.acc)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(rhs).ravel(),
                                   np.asarray(rhs_ref).ravel(),
                                   rtol=1e-5, atol=1e-5)
        assert float(state.acc.rows) == 1024.0
        print("TWO_DEVICE_MERGE_OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "TWO_DEVICE_MERGE_OK" in out.stdout
