"""Distributed (pjit) SA-leverage + Nyström KRR — the paper's pipeline on the
production mesh.

This is the deployment form of the paper's contribution: at n ~ 10^7-10^8 a
single host can neither hold the (n, d) design nor the (n, m) cross-kernel
matrix, so the pipeline shards the SAMPLE dimension over the whole mesh
(every chip owns n/chips rows) and keeps landmarks replicated:

  1. KDE        p_i = mean_j k_h(x_i - x_j)     — sharded queries against
                replicated source batches (TPU: the Pallas `kde` kernel per
                shard; here the fused-XLA oracle) -> no n x n materialisation;
  2. SA map     q_i ∝ p_i^{d/(2α)-1}            — elementwise (Eq. 6 closed
                form), embarrassingly parallel; the normaliser is one psum;
  3. Nyström    K_nm^T K_nm and K_nm^T y reduce over the sharded n axis
                (GSPMD inserts the all-reduce), the m x m solve is replicated;
  4. predict    sharded rows x replicated beta.

Everything is jit-able end to end; `lower_pipeline` is the dry-run/roofline
entry (abstract inputs, both production meshes), and tests check the sharded
path equals the single-device reference bit-for-bit (up to reduction order).

For the KDE source set we follow the paper's subsampled-KDE argument (App. E:
o(1) relative KDE error suffices): density is estimated against a uniform
m_kde-subsample of the data (m_kde ~ sqrt(n) keeps the KDE term o(n) compute
per chip while its error stays within the Thm-5 slack).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import spans
from repro.core import kernels as K
from repro.core import leverage, streaming
from repro.distributed.sharding import constrain

Array = jax.Array


class SAPipelineOut(NamedTuple):
    probs: Array        # (n,) SA sampling distribution
    d_stat: Array       # scalar
    beta: Array         # (m,) Nyström coefficients
    fitted: Array       # (n,) in-sample predictions


def _sq_dists(x: Array, y: Array) -> Array:
    x2 = jnp.sum(x * x, axis=-1)[:, None]
    y2 = jnp.sum(y * y, axis=-1)[None, :]
    return jnp.maximum(x2 + y2 - 2.0 * (x @ y.T), 0.0)


def _sq_dists_augmented(x: Array, y: Array) -> Array:
    """||x-y||^2 as ONE (d+2)-wide GEMM (§Perf cell C iter 3).

    [ -2x | ||x||^2 | 1 ] . [ y | 1 | ||y||^2 ]^T = ||x||^2 + ||y||^2 - 2x.y
    — the broadcast+add assembly of the standard expansion disappears from
    the HLO (its bytes dominated the pipeline after iter 1).  +2 columns of
    GEMM flops, exact same math up to addition order.
    """
    n, d = x.shape
    ones_x = jnp.ones((n, 1), x.dtype)
    x_aug = jnp.concatenate([-2.0 * x, jnp.sum(x * x, -1, keepdims=True),
                             ones_x], axis=1)
    ones_y = jnp.ones((y.shape[0], 1), y.dtype)
    y_aug = jnp.concatenate([y, ones_y, jnp.sum(y * y, -1, keepdims=True)],
                            axis=1)
    return jnp.maximum(x_aug @ y_aug.T, 0.0)


def kde_sharded(x: Array, kde_sample: Array, h: float) -> Array:
    """p_hat at every x_i against the (replicated) KDE subsample.

    x is row-sharded over the mesh ('batch' rule); the (n_loc, m_kde) weight
    tile lives per chip only.
    """
    m, d = kde_sample.shape
    x = constrain(x, ("batch", None))
    sq = _sq_dists(x, kde_sample)
    w = jnp.exp(-sq / (2.0 * h * h))
    norm = 1.0 / (m * (2.0 * math.pi * h * h) ** (d / 2.0))
    return constrain(norm * jnp.sum(w, axis=1), ("batch",))


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


def sa_nystrom_pipeline(
    x: Array,              # (n, d)    row-sharded design
    y: Array,              # (n,)      row-sharded responses
    kde_sample: Array,     # (m_kde, d) replicated KDE source subsample
    landmark_idx: Array,   # (m,) int  landmark rows (sampled on host from q)
    *,
    kernel: K.Matern,
    lam: float,
    kde_h: float,
    kde_method: str = "direct",     # direct | binned  (§Perf cell C, iter 1)
    knm_dtype=jnp.float32,          # bf16 halves k_nm traffic (iter 2)
) -> SAPipelineOut:
    n, d = x.shape
    # 1-2) density -> SA leverage (Eq. 6 closed form) -> sampling weights
    if kde_method == "binned":
        p = kde_binned_sharded(x, kde_h)
    else:
        p = kde_sharded(x, kde_sample, kde_h)
    raw = leverage.matern_closed_form(p, lam, kernel, d)
    raw = jnp.minimum(raw, float(n))
    total = jnp.sum(raw)                       # psum over the sharded axis
    probs = raw / total

    # 3) Nyström normal equations; n-axis reduction is the big all-reduce
    xm = x[landmark_idx]                       # gather -> replicated (m, d)
    sq = _sq_dists_augmented(x.astype(knm_dtype), xm.astype(knm_dtype))
    k_nm = kernel.from_distance(jnp.sqrt(sq)).astype(knm_dtype)
    k_nm = constrain(k_nm, ("batch", None))    # (n_loc-sharded, m)
    k_mm = kernel(xm, xm)
    m = xm.shape[0]

    # Fused dense normal equations through the streaming engine: a one-step
    # (tile=None) `tile_reduce` whose emit is the pair of fp32-accumulated
    # MXU dot_generals the historical inline path ran — the scan body
    # compiles to the same fused computation (zero-init add is exact), and
    # GSPMD still turns the n-axis contraction into the one big all-reduce.
    def emit(k_tile, y_tile):
        g = jax.lax.dot_general(k_tile, k_tile, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        r = jax.lax.dot_general(k_tile, y_tile, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return g, r

    g, rhs = streaming.tile_reduce(
        emit, k_nm, (y.astype(knm_dtype),), tile=None,
        init=(jnp.zeros((m, m), jnp.float32), jnp.zeros((m,), jnp.float32)),
        pad="zero")
    lhs = g + n * lam * k_mm
    scale = jnp.trace(lhs) / m
    lhs = lhs + (1e-6 * scale) * jnp.eye(m, dtype=lhs.dtype)
    beta = jnp.linalg.solve(lhs, rhs)          # replicated small solve

    # 4) in-sample predictions, sharded rows
    fitted = constrain((k_nm @ beta.astype(knm_dtype)).astype(jnp.float32),
                       ("batch",))
    return SAPipelineOut(probs=probs, d_stat=total / n, beta=beta,
                         fitted=fitted)


def make_pipeline_fn(kernel: K.Matern, lam: float, kde_h: float,
                     kde_method: str = "direct", knm_dtype=jnp.float32):
    return functools.partial(sa_nystrom_pipeline, kernel=kernel, lam=lam,
                             kde_h=kde_h, kde_method=kde_method,
                             knm_dtype=knm_dtype)


def abstract_inputs(n: int, d: int, m_kde: int, m: int, dtype=jnp.float32):
    """ShapeDtypeStructs for lower(): sharded x/y, replicated sample/idx."""
    from repro.distributed import sharding as shd
    act = shd.active()

    def sds(shape, dt, axes):
        if act is None:
            return jax.ShapeDtypeStruct(shape, dt)
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=act.sharding(axes, shape))

    return (
        sds((n, d), dtype, ("batch", None)),
        sds((n,), dtype, ("batch",)),
        sds((m_kde, d), dtype, (None, None)),
        sds((m,), jnp.int32, (None,)),
    )


def lower_pipeline(mesh, *, n: int, d: int = 3, nu: float = 1.5,
                   lam: float | None = None, m_kde: int | None = None,
                   m: int | None = None, kde_method: str = "direct",
                   knm_dtype=jnp.float32):
    """Dry-run entry: lower + compile the full pipeline on `mesh`."""
    from repro.distributed import sharding as shd
    lam = lam if lam is not None else 0.075 * n ** (-2.0 / 3.0)
    m = m if m is not None else int(5 * n ** (1.0 / 3.0))
    m_kde = m_kde if m_kde is not None else max(1024, int(n ** 0.5))
    kde_h = 0.15 * n ** (-1.0 / 7.0)
    kernel = K.Matern(nu=nu)
    fn = make_pipeline_fn(kernel, lam, kde_h, kde_method, knm_dtype)
    rules = {"batch": ("pod", "data", "model")}  # pure row sharding: all chips
    with shd.activate(mesh, rules):
        args = abstract_inputs(n, d, m_kde, m)
        lowered = jax.jit(fn).lower(*args)
        return lowered, lowered.compile()
