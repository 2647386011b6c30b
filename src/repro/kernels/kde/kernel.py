"""Pallas TPU kernel: tiled direct Gaussian KDE with on-chip row accumulation.

Same (bm, bn) tile structure as `pairwise`, but the (n x m) weight matrix is
never written out: each program computes a (bm, bn) tile of exp(-sq/2h^2),
reduces it over the column axis, and accumulates into the (bm, 1) output
block.  The grid's second axis is the reduction axis — the output BlockSpec
ignores it, so consecutive j-steps revisit the same output tile in VMEM
(sequential TPU grid => safe accumulation):

  j == 0:        out  = rowsum(tile)
  j  > 0:        out += rowsum(tile)

Column padding (m -> mp) is masked with the true m so padded source points
contribute no mass.  Row padding is sliced off by the wrapper.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _kde_body(q_ref, x_ref, out_ref, *, inv_two_h_sq: float, m: int, bn: int):
    j = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32)  # (bm, d)
    x = x_ref[...].astype(jnp.float32)  # (bn, d)
    qx = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    q2 = jnp.sum(q * q, axis=1)[:, None]
    x2 = jnp.sum(x * x, axis=1)[None, :]
    sq = jnp.maximum(q2 + x2 - 2.0 * qx, 0.0)
    w = jnp.exp(-sq * inv_two_h_sq)
    # mask padded source columns
    col = j * bn + jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
    w = jnp.where(col < m, w, 0.0)
    part = jnp.sum(w, axis=1)[:, None]  # (bm, 1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = part.astype(out_ref.dtype)

    @pl.when(j > 0)
    def _acc():
        out_ref[...] = out_ref[...] + part.astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("h", "m", "bm", "bn", "interpret")
)
def kde_padded(
    query: Array,
    data: Array,
    *,
    h: float,
    m: int,
    bm: int = 256,
    bn: int = 256,
    interpret: bool = False,
) -> Array:
    """Unnormalised row sums; (np, d) x (mp, d) -> (np, 1). Shapes pre-padded."""
    np_, d = query.shape
    mp, _ = data.shape
    assert np_ % bm == 0 and mp % bn == 0, (np_, mp, bm, bn)
    grid = (np_ // bm, mp // bn)
    body = functools.partial(
        _kde_body, inv_two_h_sq=1.0 / (2.0 * float(h) ** 2), m=m, bn=bn
    )
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        interpret=interpret,
        name="kde_padded",
    )(query, data)
