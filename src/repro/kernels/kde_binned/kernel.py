"""Pallas TPU kernel: cloud-in-cell deposit as one-hot matmuls on the MXU.

A deposit is a histogram: grid rows = one-hot(rows)^T . lane rows.  The TPU
formulation keeps the WHOLE d <= 3 lattice as a VMEM-resident output block
(<= 4.7 MB at the production resolutions: 1024 / 512^2 / 96^3 cells) and
streams a point stream sorted by lattice row through it:

  * the d-dim lattice is laid out 2-D as (R, C) = (g^(d-1), g): the LAST
    lattice axis is the lane axis, the leading axes are flattened into
    sublanes.  A point's base sublane row r0 and its 2^(d-1) corner planes
    (a, b, ...) over the leading axes put its mass on rows r0 + offset,
    with offsets 0, 1, g, g + 1 at d = 3 (`plane_offsets`);
  * grid (n_chunks,) — one axis, P points a step, as lane-dense (., P)
    blocks: sublane rows and last-axis base lanes (int32), the last-axis
    fraction and one weight per corner plane (float32, point weight folded
    in).  The output BlockSpec maps every step to the same block, so the
    lattice persists in VMEM across the whole stream (canonical
    accumulation: init at i == 0, += after);
  * the stream is sorted by r0 across the whole call (ops.py), so each
    chunk covers a short run of rows [first, last], prefetched into SMEM;
    every plane's rows r0 + offset are sorted as well;
  * per chunk the body builds the (C, P) lane matrix in one vectorised
    pass — weight times (1 - f) at lane b, times f at lane b + 1 — and per
    corner plane loops over the W-row windows of the lattice (W = 128,
    aligned) that the plane's rows touch: it builds the (W, P) one-hot of
    rows against the window and adds dot(one-hot, lanes^T) to grid rows
    [base, base + W) in one read-modify-write.  The loop runs over
    windows, never over points or corners.

Window bound.  Plane c of chunk i runs windows (first_i + off_c) // W to
(last_i + off_c) // W.  The chunks are sorted, so their row runs only touch
at the ends and the counts telescope: over the whole stream the kernel runs
at most 2^(d-1) * (n_chunks + Rp / W) windows (Rp = R rounded up to W),
whatever the data — at 96^3 that is at most 72 windows per plane beyond
one per chunk.  `ops.window_counts` gives the per-chunk counts.

Numerics: the same float32 sums in another order.  The one-hot operand is
exactly 0/1 and the lane matrix stays float32; the dot states
``precision=HIGHEST`` and ``preferred_element_type=float32`` itself (it
does not lean on ``jax_default_matmul_precision``), so every product is
exact and every sum is a float32 sum.  The one-hot is the plain left
operand and the lanes are contracted on their P axis ("NT"): the v5e
compiler refuses float32 dots with a transposed left operand.

Each window lands in the lattice as ONE additive delta, so the update
composes with the two-float compensated accumulator: with
``compensated=True`` the kernel carries the lattice as a (hi, lo) pair in
VMEM and folds every window in through an error-free two-sum, banking the
rounding error in lo — the same strategy `repro.core.streaming` runs across
XLA tiles, so the pair can cross a mesh psum un-collapsed.  Padded points
carry zero weight on the last real row (ops.py), so they add no window and
deposit nothing — no masking in the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.kernels import round_up
from repro.kernels import out_vma

Array = jax.Array

WIN = 128   # lattice rows per window: one MXU tile of the one-hot's rows


def window_rows(rows_dim: int) -> int:
    """Rows W of one window of an R-row lattice (R itself when smaller,
    rounded up to the 8-row sublane tile)."""
    return min(WIN, round_up(rows_dim, 8))


def plane_offsets(d: int, g: int) -> tuple[int, ...]:
    """Sublane-row offset of each of the 2^(d-1) corner planes: bit k of
    the plane index steps leading axis k, which strides g^(d-2-k) rows."""
    return tuple(sum(((c >> k) & 1) * g ** (d - 2 - k) for k in range(d - 1))
                 for c in range(2 ** (d - 1)))


def plane_windows(first, last, offset: int, win: int):
    """First and last (inclusive) W-aligned window a corner plane of a
    chunk touches, for a chunk whose sorted base rows run first..last."""
    return (first + offset) // win, (last + offset) // win


def _deposit_body(first_ref, last_ref, rb_ref, fw_ref, *out_refs,
                  offsets: tuple[int, ...], win: int, compensated: bool):
    hi_ref = out_refs[0]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        for ref in out_refs:
            ref[...] = jnp.zeros_like(ref)

    p = rb_ref.shape[-1]
    rows = rb_ref[0:1, :]                             # (1, P) base rows
    b = rb_ref[1:2, :]                                # (1, P) base lanes
    f = fw_ref[0:1, :]                                # (1, P) fractions
    lane = jax.lax.broadcasted_iota(jnp.int32, (hi_ref.shape[1], p), 0)
    lanes = (jnp.where(lane == b, 1.0 - f, 0.0)
             + jnp.where(lane == b + 1, f, 0.0))      # (C, P)
    sub = jax.lax.broadcasted_iota(jnp.int32, (win, p), 0)

    for c, off in enumerate(offsets):
        lanes_c = lanes * fw_ref[1 + c:2 + c, :]      # corner-plane weight
        rows_c = rows + off

        def window(j, carry, lanes_c=lanes_c, rows_c=rows_c):
            base = pl.multiple_of(j * win, win)
            onehot = (rows_c - base == sub).astype(jnp.float32)  # (W, P)
            delta = jax.lax.dot_general(
                onehot, lanes_c, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)   # (W, C)
            rows_w = (pl.ds(base, win), slice(None))
            cur = hi_ref[rows_w]
            if compensated:
                lo_ref = out_refs[1]
                s = cur + delta                       # TwoSum(cur, delta)
                bb = s - cur
                err = (cur - (s - bb)) + (delta - bb)
                hi_ref[rows_w] = s
                lo_ref[rows_w] = lo_ref[rows_w] + err
            else:
                hi_ref[rows_w] = cur + delta
            return carry

        w_first, w_last = plane_windows(first_ref[i], last_ref[i], off, win)
        jax.lax.fori_loop(w_first, w_last + 1, window, 0)


@functools.partial(
    jax.jit, static_argnames=("dim", "grid_size", "lanes_dim", "compensated",
                              "interpret")
)
def scatter_sorted(
    first: Array,    # (n_chunks,) int32 first base row of each chunk
    last: Array,     # (n_chunks,) int32 last base row of each chunk
    rb: Array,       # (n_chunks, 2, P) int32 base rows, last-axis base lanes
    fw: Array,       # (n_chunks, 1 + 2^(d-1), P) f32 last-axis fraction,
                     #   corner-plane weights (0 = pad)
    *,
    dim: int,        # d
    grid_size: int,  # g
    lanes_dim: int,  # C = lane-padded g
    compensated: bool = False,
    interpret: bool = False,
):
    """Core pallas_call; requires the stream sorted by base row across all
    chunks, with ``first``/``last`` each chunk's first and last row (prep
    done by ops.py).  Returns the (Rp, C) lattice, Rp = g^(d-1) rounded up
    to a whole window — a (hi, lo) pair of lattices when ``compensated``."""
    n_chunks, two, p = rb.shape
    assert two == 2 and fw.shape == (n_chunks, 1 + 2 ** (dim - 1), p), (
        rb.shape, fw.shape)
    rows_dim = grid_size ** (dim - 1)
    win = window_rows(rows_dim)
    rows_pad = round_up(rows_dim, win)
    body = functools.partial(
        _deposit_body, offsets=plane_offsets(dim, grid_size), win=win,
        compensated=compensated)
    n_out = 2 if compensated else 1
    out = pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_chunks,),
            in_specs=[pl.BlockSpec((None,) + a.shape[1:],
                                   lambda i, *_: (i, 0, 0)) for a in (rb, fw)],
            out_specs=[pl.BlockSpec((rows_pad, lanes_dim),
                                    lambda i, *_: (0, 0))
                       for _ in range(n_out)],
        ),
        out_shape=[jax.ShapeDtypeStruct((rows_pad, lanes_dim), jnp.float32,
                                        vma=out_vma(first, last, rb, fw))
                   for _ in range(n_out)],
        interpret=interpret,
        name="scatter_sorted",
    )(first, last, rb, fw)
    return tuple(out) if compensated else out[0]
