"""The binned KDE under a mesh: each chip deposits its own rows into the
lattice, one psum joins the lattices, and the read-back smooths and gathers
per bandwidth at the local rows.

This is the density stage of the pipeline (`repro.pipeline.stages`
DensityStage and CalibrateStage) when a mesh is active; the normal
equations shard through `repro.core.streaming` (`nystrom.normal_eq_state`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import spans
from repro.core import streaming

Array = jax.Array


def kde_binned_sharded(x: Array, h: float, *, grid_size: int = 96,
                       lo: Array | None = None, hi: Array | None = None,
                       tile: int | None = None,
                       backend: str | None = None,
                       accumulator: str = "plain") -> Array:
    """Paper-faithful Õ(n) KDE, sharded: each chip deposits its own rows,
    one psum joins the lattices.

    One-bandwidth wrapper over `kde_binned_sharded_multi` (identical ops for
    a single h — the multi body's per-h loop degenerates to the historical
    single-h body).  This is the KDE stage the pipeline
    (`repro.pipeline.stages.DensityStage`) runs under an active mesh.
    """
    return kde_binned_sharded_multi(x, (h,), grid_size=grid_size, lo=lo,
                                    hi=hi, tile=tile, backend=backend,
                                    accumulator=accumulator)[0]


def kde_binned_sharded_multi(x: Array, hs, *, grid_size: int = 96,
                             lo: Array | None = None, hi: Array | None = None,
                             tile: int | None = None,
                             backend: str | None = None,
                             accumulator: str = "plain") -> Array:
    """Sharded binned KDE for a bandwidth GRID: (H, n) at one deposit+psum.

    Two shard_map programs.  The deposit streams LOCAL rows through the CIC
    deposit (`kernels.dispatch.binned_scatter` — the engine-tiled XLA
    scatter or the Pallas `kde_binned` kernel per `backend`, O(tile 2^d)
    transient per chip) into a local copy of the (small, replicated) grid
    and psums the accumulator STATE across the mesh (the
    `repro.core.streaming` strategy owns the collective: the compensated
    (hi, lo) pair crosses it un-collapsed); the read-back runs the
    per-bandwidth FFT smoothing and the purely local multilinear gather.
    The deposit and the grid psum are bandwidth-independent and run ONCE
    for the whole sweep — the mesh half of the CalibrateStage contract
    (a naive sweep would psum per candidate).  Per-chip bytes stay
    O(tile + g^d); the only collective is the one grid psum.  Bounds
    (lo, hi) default to [-5, 5]^d, which covers normalised designs.

    Each program is jitted and built once per mesh, grid, deposit plan and
    row count, so a later call at the same shapes and bandwidth count
    compiles nothing: the bounds and the bandwidths enter as arguments.
    The host spans ``repro/kde/deposit`` and ``repro/kde/readback`` bound
    the two dispatches.

    2D (data x model) meshes: when the active rules map the "models"
    logical axis to a mesh axis that divides H (and "rows" divides n), the
    rows shard over the DATA axes only, the grid psums over data only, and
    the H bandwidths shard over the MODEL axis — each model-chip smooths
    and gathers only its H/M candidates instead of every chip redundantly
    running the whole (H, g^d) FFT sweep (the VMEM/grid-resolution ceiling
    ROADMAP item 5 names).  The deposit is replicated across model chips
    (it is the cheap, bandwidth-independent part); per-h outputs are
    bit-equal to the 1D data-mesh path with the same data-shard count —
    the psum has the same participants and the per-h smooth/gather is the
    same op sequence on a bandwidth sliced from a device array.  On a 1D
    mesh (no "models"-mapped axis) the historical all-axes row sharding is
    unchanged.  With no mesh, or a row count the mesh does not divide, the
    same deposit and read-back run eagerly on one device.
    """
    from repro.distributed import sharding as shd

    n, d = x.shape
    act = shd.active()
    if (lo is None) != (hi is None):
        raise ValueError("pass both lo and hi to pin the grid bounds, or "
                         "neither for the [-5, 5]^d default")
    if lo is None:
        lo = jnp.full((d,), -5.0, x.dtype)
        hi = jnp.full((d,), 5.0, x.dtype)
    spacing = (hi - lo) / (grid_size - 1)
    plan = dict(grid_size=grid_size, tile=tile, backend=backend,
                accumulator=accumulator)
    row_axes = None
    if act is not None:
        data_axes = act.spec(("rows", None), x.shape)[0]
        model_axes = act.spec(("models",), (len(hs),))[0]
        if model_axes is not None and data_axes is not None:
            # 2D path: rows over data, bandwidths over model.  The h subset
            # is an INPUT sliced by shard_map (in_spec P(model)), so each
            # chip's per-h loop runs the same traced-ops sequence as the 1D
            # path.
            row_axes = data_axes
            psum_axes = ((data_axes,) if isinstance(data_axes, str)
                         else tuple(data_axes))
        elif n % act.mesh.devices.size == 0:
            row_axes = psum_axes = tuple(act.mesh.axis_names)
            model_axes = None
    if row_axes is None:   # no mesh, or one that does not divide n
        return _readback(_deposit(x, lo, spacing, (), **plan), x, lo, spacing,
                         hs, grid_size=grid_size, n=n)
    with spans.span("repro/kde/deposit"):
        grid = _deposit_program(act.mesh, row_axes, psum_axes, **plan)(
            x, lo, spacing)
    with spans.span("repro/kde/readback"):
        return _readback_program(act.mesh, row_axes, model_axes, grid_size,
                                 n)(grid, x, lo, spacing,
                                    jnp.asarray(hs, x.dtype))


def _deposit(x_loc, lo, spacing, psum_axes, *, grid_size, tile, backend,
             accumulator):
    """The CIC count grid of ``x_loc``, its accumulator state psummed over
    ``psum_axes`` (only meaningful inside shard_map; ONE psum per sweep)."""
    from repro.kernels import dispatch

    acc = streaming.get(accumulator)
    state = dispatch.binned_scatter(x_loc, lo, spacing, grid_size,
                                    backend=backend, tile=tile,
                                    accumulator=accumulator, finalize=False)
    if psum_axes:
        state = acc.psum(state, psum_axes)
    return acc.finalize(state)


def _readback(grid, x_loc, lo, spacing, hs, *, grid_size, n):
    """(H, n_loc) densities at ``x_loc``, one row per bandwidth: the shared
    per-h op sequence (`core.kde.smooth_gather`), the same traced program
    whether h is a python float or a device scalar sliced from an array."""
    from repro.core import kde as core_kde

    return jnp.stack([
        core_kde.smooth_gather(grid, x_loc, h, lo=lo, spacing=spacing,
                               grid_size=grid_size, d=x_loc.shape[1], n=n)
        for h in hs])


@functools.lru_cache(maxsize=16)
def _deposit_program(mesh, row_axes, psum_axes, *, grid_size, tile, backend,
                     accumulator):
    from jax.sharding import PartitionSpec as P

    body = functools.partial(_deposit, psum_axes=psum_axes,
                             grid_size=grid_size, tile=tile, backend=backend,
                             accumulator=accumulator)
    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(P(row_axes, None), P(), P()),
                                 out_specs=P()))


@functools.lru_cache(maxsize=16)
def _readback_program(mesh, row_axes, model_axes, grid_size, n):
    from jax.sharding import PartitionSpec as P

    def body(grid, x_loc, lo, spacing, hs):
        return _readback(grid, x_loc, lo, spacing,
                         [hs[i] for i in range(hs.shape[0])],
                         grid_size=grid_size, n=n)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(row_axes, None), P(), P(), P(model_axes)),
        out_specs=P(model_axes, row_axes)))


@jax.jit
def grid_geometry(x: Array, h: float | Array | None = None
                  ) -> tuple[Array, Array, Array]:
    """(h, lo, hi) of the binned KDE over all rows of a row-sharded x: the
    bandwidth (Scott's rule when ``h`` is None) and the lattice bounds
    +-4h around the global data bounds, as one program."""
    from repro.core import kde as core_kde

    if h is None:
        h = core_kde.scott_bandwidth(x)
    h = jnp.asarray(h, x.dtype)
    lo, hi = core_kde.binned_bounds(x, x, h)
    return h, lo, hi
