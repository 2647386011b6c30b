"""Pallas TPU kernel: tiled stationary-kernel (Gram) matrix.

This is the O(n m d) hot spot of the whole paper pipeline — Nystrom needs
K(X, X_S) (n x d_sub) and K(X_S, X_S), and the direct KDE path needs the same
tile structure.  The TPU-native formulation:

  * grid (ceil(n/bm), ceil(m/bn)); each program owns one (bm, bn) output tile;
  * x-tile (bm, d) and y-tile (bn, d) live in VMEM; the cross term x.y^T runs
    on the MXU via dot_general with fp32 accumulation;
  * the squared distance assembly and the stationary-kernel map (Matern
    0.5/1.5/2.5 or Gaussian) are fused element-wise in VMEM — the n x m
    distance matrix is never materialised in HBM at any other precision;
  * block sizes default to 256 x 256 (MXU-aligned; VMEM footprint at d=128:
    2*(256*128) + 256*256 fp32 ~= 0.5 MB, far under the ~16 MB budget, so the
    pipeline can double-buffer).

Rows/cols beyond (n, m) come from wrapper padding; the pad region is sliced
off in ops.py, so no masking is needed here (the map is total on sq >= 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.kernels import exact_sq_dists
from repro.kernels import out_vma

Array = jax.Array


def _sq_dist_tile(x, y, exact_d: int):
    """(bm, d) x (bn, d) -> (bm, bn) squared distances.

    exact_d > 0 accumulates exact per-coordinate differences over the first
    exact_d feature columns (`core.kernels.exact_sq_dists` — 2-D VPU
    broadcasts; well-conditioned near r = 0, where the MXU expansion cancels
    catastrophically, see core.kernels.EXACT_DIST_D).  Padded feature
    columns past exact_d are all-zero and contribute nothing either way.
    """
    if exact_d > 0:
        return exact_sq_dists(x, y, exact_d)
    # MXU cross term with explicit fp32 accumulation.
    xy = jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bm, bn)
    x2 = jnp.sum(x * x, axis=1)[:, None]
    y2 = jnp.sum(y * y, axis=1)[None, :]
    return jnp.maximum(x2 + y2 - 2.0 * xy, 0.0)


def _kernel_body(x_ref, y_ref, out_ref, *, kind: str, nu: float, a: float,
                 inv_two_sigma_sq: float, exact_d: int):
    x = x_ref[...].astype(jnp.float32)  # (bm, d)
    y = y_ref[...].astype(jnp.float32)  # (bn, d)
    sq = _sq_dist_tile(x, y, exact_d)
    if kind == "gaussian":
        k = jnp.exp(-sq * inv_two_sigma_sq)
    else:
        ar = a * jnp.sqrt(sq)
        if nu == 0.5:
            k = jnp.exp(-ar)
        elif nu == 1.5:
            k = (1.0 + ar) * jnp.exp(-ar)
        else:  # nu == 2.5
            k = (1.0 + ar + ar * ar * (1.0 / 3.0)) * jnp.exp(-ar)
    out_ref[...] = k.astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("kind", "nu", "a", "sigma", "bm", "bn", "out_dtype",
                     "interpret", "exact_d"),
)
def pairwise_padded(
    x: Array,
    y: Array,
    *,
    kind: str = "matern",
    nu: float = 1.5,
    a: float = 1.0,
    sigma: float = 1.0,
    bm: int = 256,
    bn: int = 256,
    out_dtype=jnp.float32,
    interpret: bool = False,
    exact_d: int = 0,
) -> Array:
    """Core pallas_call; requires n % bm == 0 and m % bn == 0 (see ops.py)."""
    n, d = x.shape
    m, _ = y.shape
    assert n % bm == 0 and m % bn == 0, (n, m, bm, bn)
    grid = (n // bm, m // bn)
    body = functools.partial(
        _kernel_body,
        kind=kind,
        nu=float(nu),
        a=float(a),
        inv_two_sigma_sq=1.0 / (2.0 * float(sigma) ** 2),
        exact_d=int(exact_d),
    )
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), out_dtype,
                                       vma=out_vma(x, y)),
        interpret=interpret,
        name="pairwise_padded",
    )(x, y)
