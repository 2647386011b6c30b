"""jit'd public wrapper for the KDE Pallas kernel (padding + normalisation)."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core.kernels import round_up
from repro.kernels.kde import kernel as kk
from repro.kernels.kde import ref
from repro.kernels import resolve_interpret

Array = jax.Array


@functools.partial(
    jax.jit, static_argnames=("h", "bm", "bn", "interpret", "use_pallas")
)
def kde(
    query: Array,
    data: Array,
    *,
    h: float,
    bm: int = 256,
    bn: int = 256,
    interpret: bool | None = None,
    use_pallas: bool = True,
) -> Array:
    """Gaussian KDE density estimates at `query` from `data`, O(n m d) direct.

    Matches repro.core.kde.kde_direct / ref.kde to fp32 accuracy.
    """
    if not use_pallas:
        return ref.kde(query, data, h)
    interpret = resolve_interpret(interpret)
    n, d = query.shape
    m, _ = data.shape
    bm_ = min(bm, round_up(n, 8))
    bn_ = min(bn, round_up(m, 128))
    np_, mp = round_up(n, bm_), round_up(m, bn_)
    dp = round_up(d, 128) if not interpret else d
    q = jnp.pad(query, ((0, np_ - n), (0, dp - d)))
    x = jnp.pad(data, ((0, mp - m), (0, dp - d)))
    sums = kk.kde_padded(q, x, h=h, m=m, bm=bm_, bn=bn_, interpret=interpret)
    norm = 1.0 / (m * (2.0 * math.pi * h * h) ** (d / 2.0))
    return norm * sums[:n, 0]
