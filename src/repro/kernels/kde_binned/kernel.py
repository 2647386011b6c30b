"""Pallas TPU kernel: sorted segment-reduce cloud-in-cell scatter-add.

Scatter is the one stage of the binned KDE with no MXU mapping — it is
data-dependent addressing — so the TPU formulation keeps the WHOLE d <= 3
grid as a VMEM-resident output block (<= 4.7 MB at the production
resolutions: 1024 / 512^2 / 96^3 cells) and streams corner tiles through it:

  * grid (K/kc,) — one axis, the corner stream (kc = bm * 2^(d-1) corners
    per step); the output BlockSpec maps every step to the same (R, C)
    block, so the grid persists in VMEM across the whole stream (canonical
    accumulation: init at i == 0, += after);
  * the d-dim lattice is laid out 2-D as (R, C) = (g^(d-1), g): the LAST
    lattice axis is the lane axis, the leading axes are flattened into
    sublanes.  ops.py precomputes, per CORNER, the flattened sublane row
    index, the corner weight (point weight folded in), and the last-axis
    base lane / fraction the body's iota compare expands into a 2-nonzero
    lane row — then SORTS each kc-corner chunk by row and marks segment
    ends, so the body accumulates same-row corners in a (1, C) register
    vector and touches VMEM once per DISTINCT row (a segment-reduce),
    not once per corner as the historical serial per-point loop did;
  * within a program the fori_loop over the kc corners is sequential and
    the TPU grid is sequential over i, so the read-modify-write at each
    segment end is safe without atomics.

Because each segment lands in the grid as ONE additive delta, the update
composes with the two-float compensated accumulator: with
``compensated=True`` the kernel carries the grid as a (hi, lo) pair in VMEM
and folds every segment in through an error-free two-sum, banking the
rounding error in lo — the same strategy `repro.core.streaming` runs across
XLA tiles, so the pair can cross a mesh psum un-collapsed
(`dispatch.binned_scatter` no longer reroutes compensated deposits to XLA).
Padded corners carry zero weight and row 0 (ops.py), so they merge into the
first segment and deposit nothing — no masking in the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import out_vma

Array = jax.Array


def _scatter_sorted_body(rows_ref, cw_ref, blast_ref, flast_ref, segend_ref,
                         *out_refs, kc: int, compensated: bool):
    hi_ref = out_refs[0]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        for ref in out_refs:
            ref[...] = jnp.zeros_like(ref)

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, hi_ref.shape[1]), 1)

    def corner(k, acc):
        b = blast_ref[k, 0]                           # last-axis base lane
        f = flast_ref[k, 0]
        w = cw_ref[k, 0]
        acc = acc + w * (jnp.where(lane == b, 1.0 - f, 0.0)
                         + jnp.where(lane == b + 1, f, 0.0))
        end = segend_ref[k, 0] != 0                   # last corner of its row
        r = rows_ref[k, 0]

        @pl.when(end)
        def _():
            row = (pl.ds(r, 1), slice(None))
            cur = hi_ref[row]
            if compensated:
                lo_ref = out_refs[1]
                s = cur + acc                         # TwoSum(cur, acc)
                bb = s - cur
                err = (cur - (s - bb)) + (acc - bb)
                hi_ref[row] = s
                lo_ref[row] = lo_ref[row] + err
            else:
                hi_ref[row] = cur + acc

        # the accumulator resets at segment boundaries; sublane updates stay
        # vectorized (every op above is a whole (1, C) lane row)
        return jnp.where(end, jnp.zeros_like(acc), acc)

    jax.lax.fori_loop(0, kc, corner, jnp.zeros((1, hi_ref.shape[1]),
                                               jnp.float32))


@functools.partial(
    jax.jit, static_argnames=("rows_dim", "lanes_dim", "kc", "compensated",
                              "interpret")
)
def scatter_sorted(
    rows: Array,     # (K, 1) int32 flattened sublane row per corner
    cw: Array,       # (K, 1) f32 corner weight x point weight (0 = pad)
    blast: Array,    # (K, 1) int32 last-axis base lane
    flast: Array,    # (K, 1) f32 last-axis fraction
    segend: Array,   # (K, 1) int32 1 at the last corner of each row segment
    *,
    rows_dim: int,   # R = g^(d-1)
    lanes_dim: int,  # C = lane-padded g
    kc: int,         # corners per grid step (bm * 2^(d-1))
    compensated: bool = False,
    interpret: bool = False,
):
    """Core pallas_call; requires K % kc == 0 and each kc-chunk sorted by
    `rows` with `segend` marking the last corner of every row run (chunk
    prep done by ops.py).  Returns the (R, C) grid — a (hi, lo) pair of
    grids when ``compensated``."""
    k_, one = rows.shape
    assert one == 1 and k_ % kc == 0, (rows.shape, kc)
    body = functools.partial(_scatter_sorted_body, kc=kc,
                             compensated=compensated)
    n_out = 2 if compensated else 1
    out = pl.pallas_call(
        body,
        grid=(k_ // kc,),
        in_specs=[pl.BlockSpec((kc, 1), lambda i: (i, 0)) for _ in range(5)],
        out_specs=[pl.BlockSpec((rows_dim, lanes_dim), lambda i: (0, 0))
                   for _ in range(n_out)],
        out_shape=[jax.ShapeDtypeStruct((rows_dim, lanes_dim), jnp.float32,
                                        vma=out_vma(rows, cw, blast, flast,
                                                    segend))
                   for _ in range(n_out)],
        interpret=interpret,
        name="scatter_sorted",
    )(rows, cw, blast, flast, segend)
    return tuple(out) if compensated else out[0]
