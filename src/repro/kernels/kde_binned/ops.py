"""jit'd public wrapper for the binned-KDE deposit Pallas kernel.

Precomputes the vectorizable parts of the cloud-in-cell deposit — all O(n)
arrays, keeping the streaming-memory contract (the Pallas body builds the
lane rows and one-hots itself and reduces them on the MXU, see kernel.py):

  * `point_stream` — per POINT, the flattened base cell (base sublane
    row r0 over the leading d-1 lattice axes, times g, plus the last-axis
    base lane), the d fractions and the optional point weight;
  * `sort_stream` — the stream SORTED by cell, hence by r0, across the
    whole call: one stable keyed `lax.sort` whose payload rides through
    the sorting network, so no gather applies the order afterwards.
    Every corner plane's rows r0 + offset are then sorted as well, and a
    chunk of P points covers a short run of rows;
  * `chunk_stream` — the sorted stream laid out lane-dense as (n_chunks, .,
    P) blocks, P = bm points a kernel grid step: int32 rows and lanes,
    float32 last-axis fraction and one weight per corner plane (the
    product of the leading axes' (1-f, f), times the point weight), and
    each chunk's first and last row, which bound the kernel's window loop
    (`window_counts`).

n is padded to a multiple of P with zero-weight points on the last real
row, so pads add no window and deposit nothing; lane padding (g ->
128-multiples on TPU) and the window padding of the rows are sliced off
before the (g,)^d reshape.
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
    first, last, rb, fw = chunk_stream(
        sort_stream(point_stream(data, lo, spacing, g, weights=weights)),
        d, g, bm=bm)
    cp = round_up(g, 128) if not interpret else g
    out = kk.scatter_sorted(first, last, rb, fw, dim=d, grid_size=g,
                            lanes_dim=cp, compensated=compensated,
                            interpret=interpret)

    def crop(grid2d):
        return grid2d[:g ** (d - 1), :g].reshape((g,) * d).astype(data.dtype)

    if compensated:
        hi, lo_bank = out
        if finalize:
            return crop(hi + lo_bank)   # fold in f32, then cast once
        return (crop(hi), crop(lo_bank))
    return crop(out)


def point_stream(data: Array, lo: Array, spacing: Array, grid_size: int,
                 *, weights: Array | None = None) -> tuple[Array, ...]:
    """(n, d) points -> the deposit's point stream, unsorted.

    Returns ``(cells, frac_0, ..., frac_{d-1}[, weights])``, each (n,):
    the int32 flattened base cell (base sublane row * g + last-axis base
    lane), the float32 fractions, and the float32 point weights when
    given.
    """
    n, d = data.shape
    base, frac = ref.cic_prep(data, lo, spacing, grid_size)
    cells = jnp.zeros((n,), dtype=jnp.int32)
    for k in range(d):
        cells = cells * grid_size + base[:, k]
    fracs = tuple(frac[:, k].astype(jnp.float32) for k in range(d))
    tail = () if weights is None else (weights.astype(jnp.float32),)
    return (cells,) + fracs + tail


def sort_stream(stream: tuple[Array, ...]) -> tuple[Array, ...]:
    """Sort the point stream by base cell, payload carried along.

    Ordered by cell, the stream is ordered by base row.  One stable sort
    keyed on the cells, so the result is bitwise what an argsort of the
    cells applied to each array would give.
    """
    return tuple(jax.lax.sort(stream, num_keys=1, is_stable=True))


def chunk_stream(stream: tuple[Array, ...], d: int, grid_size: int, *,
                 bm: int = 256):
    """Sorted point stream -> the kernel's operands ``(first, last, rb, fw)``.

    P = bm points a chunk (n rounded up to 128 lanes when smaller).  ``rb`` is
    (n_chunks, 2, P) int32 rows and last-axis lanes; ``fw`` is (n_chunks,
    1 + 2^(d-1), P) float32: the last-axis fraction, then the weight of
    each corner plane (plane c steps leading axis k where bit k of c is
    set).  ``first``/``last`` (n_chunks,) are each chunk's first and last
    row.  Pads repeat the last row with zero weight.
    """
    cells, *rest = stream
    rows, lanes = cells // grid_size, cells % grid_size
    fracs = rest[:d]
    w = rest[d] if len(rest) > d else jnp.ones_like(fracs[0])
    n = rows.shape[0]
    planes = []
    for c in range(2 ** (d - 1)):
        cw = w
        for k in range(d - 1):
            cw = cw * (fracs[k] if (c >> k) & 1 else 1.0 - fracs[k])
        planes.append(cw)
    p = min(bm, round_up(n, 128))
    pad = round_up(n, p) - n

    def chunks(fields, fill):
        padded = [jnp.concatenate([a, jnp.broadcast_to(v, (pad,))])
                  for a, v in zip(fields, fill)]
        return jnp.stack(padded).reshape(len(fields), -1, p).transpose(1, 0, 2)

    rb = chunks((rows, lanes), (rows[-1], lanes[-1]))
    fw = chunks((fracs[d - 1], *planes), (0.0,) * (1 + len(planes)))
    return rb[:, 0, 0], rb[:, 0, -1], rb, fw


def window_counts(first: Array, last: Array, d: int, grid_size: int) -> Array:
    """Windows the kernel runs for each chunk, summed over corner planes —
    the trip counts of its window loops (kernel.py `plane_windows`)."""
    win = kk.window_rows(grid_size ** (d - 1))
    total = jnp.zeros_like(first)
    for off in kk.plane_offsets(d, grid_size):
        w_first, w_last = kk.plane_windows(first, last, off, win)
        total = total + (w_last - w_first + 1)
    return total
