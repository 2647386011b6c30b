"""Kernel density estimation — the Õ(n) density substrate of Algorithm 1.

The paper's complexity argument leans on tree / hashing KDE (ASKIT, HBE).
Those are pointer-chasing, data-dependent structures with no TPU mapping, so
(DESIGN.md §3) we provide two TPU-native estimators with the same o(1)
relative-error contract (paper Lemma 14 shows KDE error enters the leverage
error multiplicatively, so a sub-optimal KDE rate suffices):

  * ``kde_binned``  — linear-time gridded KDE for d <= 3: cloud-in-cell
    scatter of the n points onto a regular grid (O(n 2^d)), FFT convolution
    with the exactly-evaluated Gaussian window (O(g^d log g)), multilinear
    gather back at the n query points.  Binning error is O(delta^2 / h^2).

  * ``kde_direct``  — O(n m d) tiled evaluation, MXU-dominated through the
    ||x-y||^2 = ||x||^2+||y||^2-2x.y^T expansion.  This is the reference
    oracle; on TPU the Pallas kernel `repro.kernels.kde` computes the same
    sum in VMEM tiles (use it for d > 3 or small n where grids are wasteful).

The deposit stage of ``kde_binned`` streams through ``scatter_cic``: ONE
windowed scatter-add per row tile deposits each point's (2,)^d stencil in a
single update (~10x faster than the historical one-scatter-per-corner form
on CPU — the scatter loop runs n times, not n 2^d times), with a `lax.scan`
over row tiles bounding transient memory at O(tile 2^d).  On TPU the same
deposit runs as the Pallas `repro.kernels.kde_binned` kernel (VMEM-resident
grid); `repro.kernels.dispatch.binned_scatter` routes between them, and
`repro.core.distributed.kde_binned_sharded` shards the row stream over the
mesh with one grid psum.

Both estimators return *densities* (integrate to 1); bandwidth defaults to
Scott's rule.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro import spans
from repro.core import accstate, streaming

Array = jax.Array


def scott_bandwidth(x: Array) -> Array:
    """Scott's rule h = sigma_avg * n^(-1/(d+4)) (scalar bandwidth)."""
    n, d = x.shape
    sigma = jnp.mean(jnp.std(x, axis=0))
    return sigma * n ** (-1.0 / (d + 4))


def gaussian_norm(d: int, h: float | Array) -> Array:
    return (2.0 * math.pi) ** (d / 2.0) * jnp.asarray(h) ** d


def kde_direct(query: Array, data: Array, h: float | Array) -> Array:
    """Exact Gaussian KDE, O(n_query * n_data * d)."""
    q2 = jnp.sum(query * query, axis=-1)[:, None]
    d2 = jnp.sum(data * data, axis=-1)[None, :]
    sq = jnp.maximum(q2 + d2 - 2.0 * query @ data.T, 0.0)
    kern = jnp.exp(-sq / (2.0 * jnp.asarray(h) ** 2))
    return jnp.sum(kern, axis=1) / (data.shape[0] * gaussian_norm(data.shape[1], h))


# ------------------------------------------------------------ CIC deposit --

def cic_prep(points: Array, lo: Array, spacing: Array,
             grid_size: int) -> tuple[Array, Array]:
    """Fractional lattice coordinates -> (base cell (n, d) int32, frac (n, d)).

    Base cells are clipped to [0, grid_size - 2] so the (2,)^d stencil stays
    in bounds, and the fractional offset is clamped to [0, 1] to match: a
    point outside the grid gets the BOUNDARY cell's value (gather) and
    deposits all its mass in the boundary cells (scatter).  Without the frac
    clamp an out-of-range point keeps pos - base > 1 (or < 0), which turns
    the multilinear stencil into linear *extrapolation* — negative deposit
    weights and out-of-support query densities the `maximum(out, 0)` floor
    only partially masks.  With the +-4h grid margins of `kde_binned` both
    clips are no-ops for in-range data, so fitted-path numbers are
    unchanged; serving-time queries beyond the frozen grid bounds are where
    the clamp is load-bearing (tests/test_kde.py locks both directions).
    """
    pos = (points - lo[None, :]) / spacing[None, :]
    base = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, grid_size - 2)
    return base, jnp.clip(pos - base, 0.0, 1.0)


def _cic_stencil(frac: Array, weights: Array | None = None) -> Array:
    """(n, d) fracs -> (n, 2, ..., 2) multilinear deposit stencil."""
    n, d = frac.shape
    upd = None
    for k in range(d):
        wk = jnp.stack([1.0 - frac[:, k], frac[:, k]], axis=1)  # (n, 2)
        wk = wk.reshape((n,) + (1,) * k + (2,))
        upd = wk if upd is None else upd[..., None] * wk
    if weights is not None:
        upd = upd * weights.reshape((n,) + (1,) * d)
    return upd


@functools.partial(jax.jit, static_argnames=("grid_size", "tile",
                                             "accumulator", "finalize",
                                             "method", "return_state"))
def scatter_cic(points: Array, lo: Array, spacing: Array, grid_size: int,
                *, weights: Array | None = None,
                tile: int | None = None,
                accumulator: str = "plain", finalize: bool = True,
                method: str = "window", init_state: Any = None,
                return_state: bool = False):
    """Cloud-in-cell deposit of (weighted) points onto a (grid_size,)^d grid.

    ``method="window"`` (default): each point's whole (2,)^d stencil lands
    in ONE windowed scatter-add update (update_window_dims), so the serial
    scatter loop runs n times instead of n 2^d — on CPU this is the
    difference between the deposit dominating the KDE and disappearing into
    the FFT's shadow.  This is the historical path and stays bit-equal to
    the pre-engine loops.

    ``method="segment"``: the sort-by-cell + segment-reduce formulation
    (sorted like the Pallas `repro.kernels.kde_binned` deposit): flatten
    every corner to its linear cell id, sort the corner stream, and
    `segment_sum` into the flat grid — duplicate-cell collisions reduce in
    vector registers instead of serializing the scatter.  Matches "window"
    to reduction-order tolerance (rtol 1e-5 locked in
    tests/test_kde_binned_kernel.py), not bitwise.

    With `tile` set, rows stream through the engine
    (`streaming.tile_reduce`: zero-pad + zero weights on the ragged tail,
    O(tile 2^d) transient stencil) with the deposit as the engine's
    `combine`.  ``accumulator="compensated"`` carries the grid as a
    two-float (hi, lo) pair — each tile's deposit is materialized against a
    zero grid and folded in with an error-free two-sum; ``finalize=False``
    returns the accumulator state for the mesh psum in
    `core.distributed.kde_binned_sharded_multi`.  ``init_state=`` deposits
    INTO a previously returned raw state instead of a zero grid (the
    incremental absorb of `DepositState`); ``return_state=True`` returns
    the raw state.
    """
    n, d = points.shape
    if method not in ("window", "segment"):
        raise ValueError(f"unknown scatter method {method!r}; "
                         "pick 'window' or 'segment'")
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=tuple(range(1, d + 1)),
        inserted_window_dims=(),
        scatter_dims_to_operand_dims=tuple(range(d)))

    def combine_window(grid, pw):
        pts, w = pw
        base, frac = cic_prep(pts, lo, spacing, grid_size)
        return jax.lax.scatter_add(grid, base, _cic_stencil(frac, w), dnums)

    def combine_segment(grid, pw):
        pts, w = pw
        base, frac = cic_prep(pts, lo, spacing, grid_size)
        t = pts.shape[0]
        # linear cell ids of ALL 2^d corners: (t, 2^d), row-major lattice
        ids = jnp.zeros((t,), jnp.int32)
        for k in range(d):
            ids = ids * grid_size + base[:, k]
        # stencil axis k (lattice dim k) is bit d-1-k of the flat corner
        # index, and dim k's linear stride is grid_size^(d-1-k) — so the
        # offset is c's bits read as base-grid_size digits
        offs = jnp.array([sum(((c >> j) & 1) * grid_size ** j
                              for j in range(d)) for c in range(2 ** d)],
                         jnp.int32)
        flat_ids = (ids[:, None] + offs[None, :]).reshape(-1)
        vals = _cic_stencil(frac, w).reshape(-1)
        order = jnp.argsort(flat_ids)
        seg = jax.ops.segment_sum(vals[order], flat_ids[order],
                                  num_segments=grid_size ** d,
                                  indices_are_sorted=True)
        return grid + seg.reshape(grid.shape).astype(grid.dtype)

    combine = combine_window if method == "window" else combine_segment

    acc = streaming.get(accumulator)
    init = jnp.zeros((grid_size,) * d, dtype=points.dtype)
    if tile is None or tile >= n:
        # one-shot deposit: weights=None skips the stencil multiply entirely
        start = acc.init(init) if init_state is None else init_state
        state = acc.add(start, (points, weights), combine)
        if return_state:
            return state
        return acc.finalize(state) if finalize else state
    w = jnp.ones((n,), points.dtype) if weights is None else weights
    return streaming.tile_reduce(
        lambda pts, wt: (pts, wt), points, (w,), tile=tile, init=init,
        combine=combine, accumulator=accumulator, pad="zero",
        finalize=finalize, init_state=init_state, return_state=return_state)


@functools.partial(jax.jit, static_argnames=("grid_size",))
def gather_cic(grid: Array, query: Array, lo: Array, spacing: Array,
               grid_size: int) -> Array:
    """Multilinear (CIC-adjoint) interpolation of `grid` at the queries."""
    d = query.shape[1]
    base, frac = cic_prep(query, lo, spacing, grid_size)
    out = jnp.zeros(query.shape[0], dtype=grid.dtype)
    for corner in range(2 ** d):
        offs = jnp.array([(corner >> k) & 1 for k in range(d)],
                         dtype=jnp.int32)
        idx = base + offs[None, :]
        w = jnp.prod(jnp.where(offs[None, :] == 1, frac, 1.0 - frac), axis=1)
        out = out + w * grid[tuple(idx[:, k] for k in range(d))]
    return out


@functools.partial(jax.jit, static_argnames=("grid_size", "d"))
def _fft_smooth(grid: Array, spacing: Array, h: Array, grid_size: int, d: int) -> Array:
    """Convolve the count grid with the exact Gaussian window via padded FFT."""
    pad = 2 * grid_size
    axes_freq = []
    for k in range(d):
        # Centered offsets on the padded circle: 0, 1, ..., pad/2, -(pad/2-1), ..., -1
        offs = jnp.arange(pad)
        offs = jnp.where(offs > pad // 2, offs - pad, offs).astype(grid.dtype)
        axes_freq.append(offs * spacing[k])
    # Separable Gaussian: product over dims of exp(-x_k^2 / (2h^2)).
    window = jnp.ones((pad,) * d, dtype=grid.dtype)
    for k in range(d):
        shape = [1] * d
        shape[k] = pad
        window = window * jnp.exp(-(axes_freq[k] ** 2) / (2.0 * h ** 2)).reshape(shape)
    padded = jnp.zeros((pad,) * d, dtype=grid.dtype)
    padded = padded.at[tuple(slice(0, grid_size) for _ in range(d))].set(grid)
    out = jnp.fft.irfftn(
        jnp.fft.rfftn(padded) * jnp.fft.rfftn(window), s=(pad,) * d
    )
    return out[tuple(slice(0, grid_size) for _ in range(d))]


def binned_bounds(query: Array, data: Array, h: Array) -> tuple[Array, Array]:
    """Grid bounds with +-4h margins (shared by local and sharded paths)."""
    lo = jnp.minimum(jnp.min(data, axis=0), jnp.min(query, axis=0)) - 4.0 * h
    hi = jnp.maximum(jnp.max(data, axis=0), jnp.max(query, axis=0)) + 4.0 * h
    return lo, hi


def smooth_gather(grid: Array, query: Array, h: Array, *, lo: Array,
                  spacing: Array, grid_size: int, d: int, n: Array) -> Array:
    """One bandwidth's densities from a deposited count grid: FFT smooth ->
    CIC gather -> clamp + 1/(n * (2 pi h^2)^{d/2}) normalization.

    THE per-h op sequence of every binned-KDE consumer (`kde_binned_multi`,
    `densities_from_state`, `distributed.kde_binned_sharded_multi`) — kept
    in one place so bit-parity claims across those paths reduce to "same
    deposit, same grid".  Traced-h safe: `h` may be a device scalar sliced
    from a shard_map input (the model-axis-sharded bandwidth sweep), and
    every h op is jnp, so the traced program is identical regardless of
    which chip holds which bandwidth — the foundation of the 2D-vs-1D-mesh
    per-h bit equality.  `n` is the (possibly fractional, decayed)
    normalizing row count.
    """
    with spans.span("repro/kde/smooth"):
        smooth = _fft_smooth(grid, spacing, jnp.asarray(h, grid.dtype),
                             grid_size, d)
    with spans.span("repro/kde/readback"):
        out = gather_cic(smooth, query, lo, spacing, grid_size)
        return jnp.maximum(out, 0.0) / (n * gaussian_norm(d, h))


# ------------------------------------------------------------ deposit state --

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DepositState:
    """First-class CIC deposit state: a mergeable count grid + its geometry.

    The deposit is bandwidth-independent on a fixed grid geometry (the
    CalibrateStage shared-deposit contract), so ONE absorbed state serves
    every bandwidth: `densities_from_state` re-runs only the O(g^d log g)
    FFT smooth + gather per query/h.  Monoid ops mirror `NormalEqState`:
    `deposit_init` / `deposit_absorb` / `deposit_merge` / `deposit_decay`
    / `deposit_finalize`.  Points outside the frozen bounds clamp to the
    boundary cells (`cic_prep`) — re-init with wider bounds if the stream
    drifts past the fitted support.
    """

    acc: accstate.AccState      # value = (grid_size,)^d grid strategy state
    lo: Array                   # (d,) grid origin
    spacing: Array              # (d,) cell size
    grid_size: int = 0          # static: cells per axis
    tile: int | None = None     # static: deposit slab rows
    accumulator: str = "plain"  # static
    method: str = "window"      # static: scatter formulation
    backend: str | None = None  # static: dispatch backend

    def tree_flatten(self):
        return ((self.acc, self.lo, self.spacing),
                (self.grid_size, self.tile, self.accumulator, self.method,
                 self.backend))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        acc, lo, spacing = leaves
        grid_size, tile, accumulator, method, backend = aux
        return cls(acc=acc, lo=lo, spacing=spacing, grid_size=grid_size,
                   tile=tile, accumulator=accumulator, method=method,
                   backend=backend)


def deposit_init(lo: Array, hi: Array, grid_size: int, *,
                 dtype=jnp.float32, tile: int | None = None,
                 accumulator: str = "plain", method: str = "window",
                 backend: str | None = None) -> DepositState:
    """Zero deposit state on the [lo, hi] grid (kde_binned_multi geometry:
    spacing = (hi - lo) / (grid_size - 1))."""
    lo = jnp.asarray(lo, dtype)
    d = lo.shape[0]
    spacing = (jnp.asarray(hi, dtype) - lo) / (grid_size - 1)
    zeros = jnp.zeros((grid_size,) * d, dtype)
    return DepositState(acc=accstate.init(accumulator, zeros), lo=lo,
                        spacing=spacing, grid_size=grid_size, tile=tile,
                        accumulator=accumulator, method=method,
                        backend=backend)


def deposit_absorb(state: DepositState, points: Array,
                   weights: Array | None = None) -> DepositState:
    """Deposit a new point chunk into the grid — O(chunk * 2^d).

    Routes through `kernels.dispatch.binned_scatter` so the backend knob
    is honored; on the XLA path the deposit lands directly in the carried
    state (a tile-aligned chain of absorbs is bit-equal to the one-shot
    deposit), on Pallas the chunk grid is built fresh and merged.
    """
    from repro.kernels import dispatch  # deferred: core -> kernels

    n = points.shape[0]
    value = dispatch.binned_scatter(
        points, state.lo, state.spacing, state.grid_size,
        backend=state.backend, weights=weights, tile=state.tile,
        accumulator=state.accumulator, method=state.method,
        init_state=state.acc.value, return_state=True)
    steps = 1 if state.tile is None else -(-n // min(state.tile, max(n, 1)))
    acc = accstate.AccState(value=value, rows=state.acc.rows + n,
                            steps=state.acc.steps + steps,
                            spec=state.acc.spec)
    return dataclasses.replace(state, acc=acc)


def deposit_merge(a: DepositState, b: DepositState) -> DepositState:
    """Combine two deposits built on the SAME grid geometry (caller's
    contract, like `nystrom.normal_eq_merge`)."""
    return dataclasses.replace(a, acc=accstate.merge(a.acc, b.acc))


def deposit_decay(state: DepositState, gamma: float) -> DepositState:
    """Exponential forgetting of the count grid ((hi, lo) domain)."""
    return dataclasses.replace(state, acc=accstate.decay(state.acc, gamma))


def deposit_finalize(state: DepositState) -> Array:
    """Collapse to the (grid_size,)^d count grid."""
    return accstate.finalize(state.acc)


def densities_from_state(state: DepositState, query: Array,
                         h: float | Array) -> Array:
    """Densities at `query` from an absorbed deposit — the online twin of
    `kde_binned` (normalized by the state's effective, possibly decayed,
    row count)."""
    d = state.lo.shape[0]
    grid = deposit_finalize(state)
    h = jnp.asarray(h, grid.dtype)
    n_eff = jnp.maximum(state.acc.rows.astype(grid.dtype), 1.0)
    return smooth_gather(grid, query, h, lo=state.lo, spacing=state.spacing,
                         grid_size=state.grid_size, d=d, n=n_eff)


def kde_binned(
    query: Array,
    data: Array,
    h: float | Array,
    grid_size: int = 256,
    *,
    lo: Array | None = None,
    hi: Array | None = None,
    backend: str | None = None,
    tile: int | None = None,
    interpret: bool | None = None,
    accumulator: str = "plain",
) -> Array:
    """Linear-time binned Gaussian KDE for d <= 3 (see module docstring).

    backend/tile/interpret/accumulator configure the deposit stage only (see
    `repro.kernels.dispatch.binned_scatter`): 'pallas' runs the tiled VMEM
    scatter kernel, 'xla' (CPU/GPU default) the windowed streaming scatter
    with `tile` rows per engine slab.  lo/hi pin the grid bounds (default:
    data bounds +-4h) — pass the bounds of a WIDER bandwidth to evaluate
    several h on one shared grid (`kde_binned_multi` parity).
    """
    return kde_binned_multi(query, data, (h,), grid_size, lo=lo, hi=hi,
                            backend=backend, tile=tile, interpret=interpret,
                            accumulator=accumulator)[0]


def kde_binned_multi(
    query: Array,
    data: Array,
    hs: "Sequence[float | Array]",
    grid_size: int = 256,
    *,
    lo: Array | None = None,
    hi: Array | None = None,
    backend: str | None = None,
    tile: int | None = None,
    interpret: bool | None = None,
    accumulator: str = "plain",
) -> Array:
    """Binned KDE for a bandwidth GRID at one deposit cost: (H, n) densities.

    The O(n 2^d) CIC deposit is bandwidth-independent once the grid geometry
    is fixed, so a bandwidth sweep scatters the points ONCE and only re-runs
    the O(g^d log g) FFT smooth + O(n 2^d) gather per h — the KDE half of
    `pipeline.stages.CalibrateStage`'s shared-expensive-work contract.  Grid
    bounds default to +-4·max(hs) margins (every candidate's support fits);
    row i is bit-equal to `kde_binned(query, data, hs[i], lo=lo, hi=hi)` on
    those bounds (same deposit, same per-h ops).
    """
    n, d = data.shape
    if d > 3:
        raise ValueError("kde_binned supports d <= 3; use kde_direct / Pallas kde")
    hs = [jnp.asarray(h, dtype=data.dtype) for h in hs]
    if (lo is None) != (hi is None):
        raise ValueError("pass both lo and hi to pin the grid bounds, or "
                         "neither for the +-4*max(h) data bounds")
    from repro.kernels import dispatch  # deferred: core -> kernels at call time
    with spans.span("repro/kde/deposit"):
        if lo is None:
            h_max = hs[0]
            for h in hs[1:]:
                h_max = jnp.maximum(h_max, h)
            lo, hi = binned_bounds(query, data, h_max)
        spacing = (hi - lo) / (grid_size - 1)
        grid = dispatch.binned_scatter(data, lo, spacing, grid_size,
                                       backend=backend, tile=tile,
                                       interpret=interpret,
                                       accumulator=accumulator)
    return jnp.stack([smooth_gather(grid, query, h, lo=lo, spacing=spacing,
                                    grid_size=grid_size, d=d, n=n)
                      for h in hs])


def default_grid_size(d: int) -> int:
    """Binned-KDE resolution per axis: total grid stays ~1e6 cells."""
    return {1: 1024, 2: 512, 3: 96}.get(d, 96)


def estimate_densities(
    x: Array,
    h: float | Array | None = None,
    method: str = "auto",
    grid_size: int | None = None,
    *,
    backend: str | None = None,
    tile: int | None = None,
    accumulator: str = "plain",
) -> Array:
    """Self-density p_hat(x_i) for all sample points (leave-self-in, as KDE).

    method: 'binned' | 'direct' | 'auto' (binned when d <= 3 else direct).
    grid_size: binned-KDE resolution per axis; default scales with d so the
    total grid stays ~1e6 cells (1024 / 512 / 96 for d = 1 / 2 / 3) — Scott
    bandwidths are several bins wide at these resolutions (verified in
    tests/test_kde.py), so accuracy is unchanged while the d=3 FFT drops from
    256^3 = 16.8M cells to < 1M.
    backend/tile: deposit-stage execution knobs (binned path only).
    """
    if h is None:
        with spans.span("repro/kde/bandwidth"):
            h = scott_bandwidth(x)
    d = x.shape[1]
    if grid_size is None:
        grid_size = default_grid_size(d)
    if method == "auto":
        method = "binned" if d <= 3 else "direct"
    if method == "binned":
        return kde_binned(x, x, h, grid_size=grid_size, backend=backend,
                          tile=tile, accumulator=accumulator)
    if method == "direct":
        return kde_direct(x, x, h)
    raise ValueError(f"unknown KDE method {method!r}")
