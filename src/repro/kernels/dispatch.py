"""Backend dispatch: route kernel-matrix hot spots through Pallas or XLA.

The core solvers (`nystrom`, `rls`) are written against an abstract
kernel-matrix contract; this module decides, once, which implementation
serves it:

  * ``pallas`` — the tiled Pallas kernels (`repro.kernels.pairwise` /
    `repro.kernels.gram`).  Native on TPU; off-TPU they run in interpret
    mode, which is correct but slow — useful for validation only.
  * ``xla``    — the fused pure-jnp references in `repro.core.kernels` and
    the lax.scan streaming path.  The right choice on CPU/GPU.
  * ``auto``   — ``pallas`` on TPU, ``xla`` elsewhere: the platform
    decides, never the environment.

Imports of the Pallas packages are deferred to call time so `repro.core`
never depends on `repro.kernels` at import (the reverse edge already
exists: pairwise/gram ops adapt `repro.core.kernels` objects).
"""

from __future__ import annotations

import jax

from repro.core import kernels as core_kernels

Array = jax.Array

BACKENDS = ("auto", "xla", "pallas")


def resolve(backend: str | None = None) -> str:
    """'auto'/None -> 'pallas' on TPU else 'xla'; explicit names pass through."""
    backend = backend or "auto"
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend not in ("xla", "pallas"):
        raise ValueError(f"unknown backend {backend!r}; pick from {BACKENDS}")
    return backend


def kernel_matrix(kernel: core_kernels.Kernel, x: Array,
                  y: Array | None = None, *, backend: str | None = None,
                  **kw) -> Array:
    """K(x, y) through the resolved backend (Pallas `pairwise` on TPU)."""
    if resolve(backend) == "pallas":
        from repro.kernels.pairwise import ops as pw_ops
        return pw_ops.kernel_matrix(kernel, x, y, **kw)
    return core_kernels.kernel_matrix(kernel, x, y)


def resolve_plan(op: str, n: int, m: int, d: int, *,
                 dtype=None, backend: str | None = None,
                 accumulator: str = "plain",
                 precision: str | None = "fp32",
                 num_models: int = 1):
    """Autotuned execution plan for a streamed op (`repro.tuning`).

    This is THE boundary where ``tile=None`` (and Pallas bm/bn defaults)
    become concrete integers: the roofline-ranked, optionally
    micro-benchmarked, cache-persisted choice for (device, backend, op,
    shape bucket).  Pure shape plumbing when precision is pinned — the
    plan never perturbs numerics, so op(tile=None) is bit-equal to
    op(tile=plan.tile).  ``precision=None`` (gram only) asks the model to
    resolve the (tile, precision) pair JOINTLY: the plan's ``precision``
    field then carries the chosen Gram-contraction mode.

    ``num_models`` widens the landmark block for MANY-MODEL batched
    streams (`repro.core.nystrom.fit_streaming_batched`): each tile step
    there materializes one (tile, m) kernel slab PER locally held model —
    the same transient footprint as a single (tile, m * num_models) slab —
    so the planner must budget against the widened block or the batched
    scan would pick a tile sized for one model and blow the slab budget
    num_models-fold.  Pass the PER-CHIP model count (after model-axis
    sharding), not the global batch.
    """
    import jax.numpy as jnp

    from repro import tuning
    return tuning.plan_for(op, int(n), int(m) * max(1, int(num_models)),
                           int(d),
                           dtype=dtype if dtype is not None else jnp.float32,
                           backend=resolve(backend), accumulator=accumulator,
                           precision=precision)


def resolve_tile(op: str, n: int, m: int, d: int, *,
                 dtype=None, backend: str | None = None,
                 accumulator: str = "plain",
                 precision: str | None = "fp32",
                 num_models: int = 1) -> int:
    """`resolve_plan(...).tile` — the engine-tile shorthand the streaming
    entry points (`repro.core.nystrom`) use for their ``tile=None``."""
    return resolve_plan(op, n, m, d, dtype=dtype, backend=backend,
                        accumulator=accumulator, precision=precision,
                        num_models=num_models).tile


def gram_accumulate(kernel: core_kernels.Kernel, x: Array, y: Array,
                    w: Array, *, backend: str | None = None,
                    tile: int | None = None, interpret: bool | None = None,
                    accumulator: str = "plain", finalize: bool = True,
                    init_state=None, return_state: bool = False,
                    precision: str | None = None, **kw) -> tuple:
    """(K_nm^T K_nm, K_nm^T w) through the resolved backend.

    The Pallas path is the fused one-pass `gram` kernel (row/column blocks
    ``bm``/``bn``, autotuned through `resolve_plan` unless passed
    explicitly); the XLA path is the engine-tiled row-slab accumulation in
    `repro.core.nystrom` (`streaming.tile_reduce`) with `tile` rows per
    step — ``tile=None`` means autotune.  Neither ever materializes the
    (n, m) cross-kernel matrix.

    Both backends implement the same ``accumulator`` strategies
    (`repro.core.streaming`): "plain" (historical fp32 running sum) and
    "compensated" (two-float error-carrying sum — a two-float VMEM
    accumulator inside the Pallas body).  ``finalize=False`` returns the
    raw accumulator state for a cross-chip psum (`streaming.mesh_reduce`).

    ``precision`` picks the Gram-contraction mode on both backends
    (`repro.core.precision`: "fp32" | "bf16x2" | "bf16x3").  ``None``
    resolves from the autotune plan when the tiling is being resolved
    anyway (tile/bm/bn None) and to the historical "fp32" when the caller
    pinned the tiling explicitly — an explicit-tile call stays bit-equal
    to pre-precision code.

    ``init_state=`` continues a prior raw state (first-class accumulator
    state, `repro.core.accstate`): the XLA scan threads it through the
    carry (tile-aligned chains are bit-equal to one fold); the Pallas
    kernel's VMEM accumulator cannot be seeded, so the chunk is reduced
    fresh and merged via the strategy's `merge`.  ``return_state=True``
    returns the raw state on either backend.
    """
    from repro.core import streaming as streaming_mod

    if resolve(backend) == "pallas":
        from repro.kernels.gram import ops as gram_ops
        if "bm" not in kw or "bn" not in kw:
            plan = resolve_plan("gram", x.shape[0], y.shape[0], x.shape[1],
                                dtype=x.dtype, backend="pallas",
                                accumulator=accumulator, precision=precision)
            kw.setdefault("bm", plan.bm)
            kw.setdefault("bn", plan.bn)
            if precision is None:
                precision = plan.precision
        want_raw = return_state or not finalize or init_state is not None
        state = gram_ops.gram_matrix(kernel, x, y, w, interpret=interpret,
                                     accumulator=accumulator,
                                     finalize=not want_raw,
                                     precision=precision or "fp32", **kw)
        if init_state is not None:
            state = streaming_mod.get(accumulator).merge(init_state, state)
        if return_state or not finalize:
            return state
        return streaming_mod.get(accumulator).finalize(state) \
            if want_raw else state
    from repro.core import nystrom
    if tile is None:
        plan = resolve_plan("gram", x.shape[0], y.shape[0], x.shape[1],
                            dtype=x.dtype, backend="xla",
                            accumulator=accumulator, precision=precision)
        tile = plan.tile
        if precision is None:
            precision = plan.precision
    return nystrom.scan_normal_eq(kernel, x, y, w, tile=tile,
                                  accumulator=accumulator, finalize=finalize,
                                  init_state=init_state,
                                  return_state=return_state,
                                  precision=precision or "fp32")


def binned_scatter(data: Array, lo: Array, spacing: Array, grid_size: int,
                   *, backend: str | None = None, weights: Array | None = None,
                   tile: int | None = None, bm: int | None = None,
                   interpret: bool | None = None,
                   accumulator: str = "plain", finalize: bool = True,
                   init_state=None, return_state: bool = False,
                   method: str = "window"):
    """Cloud-in-cell deposit onto a (grid_size,)^d grid, resolved backend.

    The deposit stage of the binned KDE (`repro.core.kde.kde_binned`).  The
    Pallas path (`repro.kernels.kde_binned`) keeps the grid VMEM-resident,
    sorts the points by lattice row once for the whole call, and reduces
    each ``bm``-point chunk (autotuned via `resolve_plan` when None) into
    the grid with one-hot matmuls on the MXU: per corner plane, one
    (128, bm) one-hot times the chunk's lane rows per 128-row window the
    plane touches — at most 2^(d-1) (n_chunks + R / 128) windows over R
    lattice rows, whatever the data.  The dot is float32 at an explicit
    ``HIGHEST`` with an exact 0/1 one-hot: the same float32 sums in
    another order.  The XLA path is the windowed scatter-add in
    `repro.core.kde.scatter_cic` (engine-tiled `tile`-row slabs via
    `streaming.tile_reduce`; ``tile=None`` means autotune).  Both match
    the corner-loop oracle `repro.kernels.kde_binned.ref.binned_grid` to
    reduction-order tolerance.

    ``accumulator="compensated"`` carries the grid as a two-float (hi, lo)
    pair across tiles on BOTH backends — the Pallas kernel two-sums each
    window's delta into a VMEM lo grid, so compensated deposits stay on
    Pallas.  ``finalize=False`` returns the accumulator state —
    structurally identical across backends — for a mesh psum
    (`core.distributed.kde_binned_sharded_multi`).

    The deposit is bandwidth-independent (only the grid geometry enters),
    which is why `kde.kde_binned_multi` / the CalibrateStage bandwidth sweep
    call this ONCE per grid and amortize it across every h candidate — the
    contract `kde.DepositState` makes first-class (state carries geometry,
    never bandwidth).  ``init_state=``/``return_state=`` thread raw
    accumulator state exactly like `gram_accumulate`: carried through the
    XLA scan, fresh-then-merged on Pallas.  ``method`` picks the XLA
    scatter formulation (`kde.scatter_cic`; ignored on Pallas, which is
    always the one-hot window reduction).
    """
    from repro.core import streaming as streaming_mod

    if resolve(backend) == "pallas":
        from repro.kernels.kde_binned import ops as kb_ops
        if bm is None:
            bm = resolve_plan("deposit", data.shape[0], grid_size,
                              data.shape[1], dtype=data.dtype,
                              backend="pallas", accumulator=accumulator).bm
        want_raw = return_state or not finalize or init_state is not None
        state = kb_ops.binned_scatter(data, lo, spacing, grid_size,
                                      weights=weights, bm=bm,
                                      interpret=interpret,
                                      accumulator=accumulator,
                                      finalize=not want_raw)
        if init_state is not None:
            state = streaming_mod.get(accumulator).merge(init_state, state)
        if return_state or not finalize:
            return state
        return streaming_mod.get(accumulator).finalize(state) \
            if want_raw else state
    from repro.core import kde as core_kde
    if tile is None:
        tile = resolve_tile("deposit", data.shape[0], grid_size,
                            data.shape[1], dtype=data.dtype, backend="xla",
                            accumulator=accumulator)
    return core_kde.scatter_cic(data, lo, spacing, grid_size,
                                weights=weights, tile=tile,
                                accumulator=accumulator, finalize=finalize,
                                method=method, init_state=init_state,
                                return_state=return_state)
