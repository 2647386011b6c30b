"""jit'd public wrapper for the binned-KDE scatter Pallas kernel.

Precomputes the vectorizable parts of the cloud-in-cell deposit — all O(n)
arrays, keeping the streaming-memory contract (the Pallas body builds each
corner's 2-nonzero lane row itself and is otherwise a pure segment-reduce,
see kernel.py):

  * `rows`  — per corner (2^(d-1) per point), the flattened sublane row
    index of the stencil corner over the leading d-1 lattice axes;
  * `cw`    — the matching product-of-(1-f, f) corner weight, scaled by
    the optional point weight (zeroed on padded corners, so no masking is
    needed in the kernel);
  * `blast` / `flast` — the last-axis base lane + fraction the body's iota
    compare expands into the lane deposit row;
  * per kc-corner chunk (kc = bm * 2^(d-1), one kernel grid step), the
    corner stream is SORTED by `rows` — one stable keyed `lax.sort` whose
    payload (`cw`, `blast`, `flast`) rides through the sorting network,
    so no gather applies the order afterwards — and `segend` marks the
    last corner of every equal-row run.  The kernel then performs one VMEM
    read-modify-write per distinct row instead of one per corner, which
    both vectorizes duplicate-cell collisions and exposes each segment as
    an additive delta the compensated (hi, lo) accumulator can two-sum.
    The sort is keyed on `rows` alone and stable, so corners of one
    segment keep their stream order and are summed in it.

Rows are padded to bm multiples (zero weight, row 0 — the pads sort into
the first segment and deposit nothing); lane padding (g -> 128-multiples
on TPU) is sliced off before the (g,)^d reshape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.kernels import round_up
from repro.kernels.kde_binned import kernel as kk
from repro.kernels.kde_binned import ref
from repro.kernels import resolve_interpret

Array = jax.Array


@functools.partial(
    jax.jit, static_argnames=("grid_size", "bm", "interpret", "use_pallas",
                              "accumulator", "finalize")
)
def binned_scatter(
    data: Array,
    lo: Array,
    spacing: Array,
    grid_size: int,
    *,
    weights: Array | None = None,
    bm: int = 256,
    interpret: bool | None = None,
    use_pallas: bool = True,
    accumulator: str = "plain",
    finalize: bool = True,
):
    """(n, d) points -> (grid_size,)^d CIC mass grid (Pallas path).

    Matches `ref.binned_grid` / `repro.core.kde.scatter_cic` to fp32
    reduction-order tolerance.  use_pallas=False falls back to the corner-
    loop oracle; interpret=None resolves to True off-TPU.

    ``accumulator="compensated"`` runs the kernel's two-float (hi, lo)
    grid (kernel.py) — the same strategy `repro.core.streaming` uses, so
    with ``finalize=False`` the returned (hi, lo) state matches the XLA
    engine's and can cross a mesh psum un-collapsed
    (`core.distributed.kde_binned_sharded_multi`).
    """
    n, d = data.shape
    if not 1 <= d <= 3:
        raise ValueError(f"binned_scatter supports 1 <= d <= 3, got d={d}")
    compensated = accumulator == "compensated"
    if not use_pallas:
        grid = ref.binned_grid(data, lo, spacing, grid_size, weights=weights)
        if compensated and not finalize:
            return (grid, jnp.zeros_like(grid))
        return grid
    interpret = resolve_interpret(interpret)
    g = grid_size
    rows_s, cw_s, blast_s, flast_s, segend = sort_chunks(
        *corner_chunks(data, lo, spacing, g, weights=weights, bm=bm))
    cp = round_up(g, 128) if not interpret else g

    flat = lambda a: a.reshape(-1, 1)  # noqa: E731
    out = kk.scatter_sorted(
        flat(rows_s), flat(cw_s), flat(blast_s), flat(flast_s), flat(segend),
        rows_dim=g ** (d - 1), lanes_dim=cp, kc=rows_s.shape[1],
        compensated=compensated, interpret=interpret,
    )

    def crop(grid2d):
        return grid2d[:, :g].reshape((g,) * d).astype(data.dtype)

    if compensated:
        hi, lo_bank = out
        if finalize:
            return crop(hi + lo_bank)   # fold in f32, then cast once
        return (crop(hi), crop(lo_bank))
    return crop(out)


def corner_chunks(data: Array, lo: Array, spacing: Array, grid_size: int,
                  *, weights: Array | None = None, bm: int = 256):
    """(n, d) points -> the deposit's corner stream, unsorted, in chunks.

    Returns ``(rows, cw, blast, flast)``, each (n_chunks, kc) with
    kc = bm' * 2^(d-1) corners per kernel grid step (bm' = bm, or n
    rounded up to 8 when smaller), in point-major corner order; rows are
    padded to bm' multiples with zero-weight corners on row 0 and lane 0.
    """
    n, d = data.shape
    g = grid_size
    base, frac = ref.cic_prep(data, lo, spacing, g)

    # Sublane rows + corner weights over the leading d-1 lattice axes.
    n_sub = 2 ** (d - 1)
    rows = jnp.zeros((n, n_sub), dtype=jnp.int32)
    cw = jnp.ones((n, n_sub), dtype=jnp.float32)
    for c in range(n_sub):
        r = jnp.zeros((n,), dtype=jnp.int32)
        w = (jnp.ones((n,), dtype=jnp.float32) if weights is None
             else weights.astype(jnp.float32))
        for k in range(d - 1):
            o = (c >> k) & 1
            r = r * g + base[:, k] + o
            w = w * (frac[:, k] if o else 1.0 - frac[:, k])
        rows = rows.at[:, c].set(r)
        cw = cw.at[:, c].set(w)

    # Last-axis base lane + fraction (the body expands these to lane rows).
    blast = jnp.broadcast_to(base[:, d - 1][:, None], (n, n_sub))
    flast = jnp.broadcast_to(
        frac[:, d - 1][:, None].astype(jnp.float32), (n, n_sub))

    bm_ = min(bm, round_up(n, 8))
    np_ = round_up(n, bm_)
    kc = bm_ * n_sub

    # Flatten (point, corner) into the corner stream, kc corners a chunk.
    # Pads (zero weight) carry row 0 and sort into the first segment
    # harmlessly.
    def chunks(a):
        return jnp.pad(a, ((0, np_ - n), (0, 0))).reshape(-1, kc)

    return chunks(rows), chunks(cw), chunks(blast), chunks(flast)


def sort_chunks(rows: Array, cw: Array, blast: Array, flast: Array):
    """Sort each chunk of the corner stream by row; flag segment ends.

    One stable sort keyed on ``rows`` carries ``cw``, ``blast`` and
    ``flast`` along, so the result is bitwise what an argsort of ``rows``
    applied to each array would give.  Returns ``(rows, cw, blast, flast,
    segend)`` with ``segend`` 1 on the last corner of every equal-row run
    in a chunk — the kernel's one-RMW-per-distinct-row contract.
    """
    rows_s, cw_s, blast_s, flast_s = jax.lax.sort(
        (rows, cw, blast, flast), dimension=1, num_keys=1, is_stable=True)
    segend = jnp.concatenate(
        [rows_s[:, 1:] != rows_s[:, :-1],
         jnp.ones((rows_s.shape[0], 1), bool)], axis=1).astype(jnp.int32)
    return rows_s, cw_s, blast_s, flast_s, segend
