"""jit'd public wrapper for the fused gram Pallas kernel.

Handles padding (rows to bm multiples at the ROW_SENTINEL coordinate so they
map to kernel value 0, landmarks to bn multiples, features to lane width),
dispatches Pallas (TPU) vs interpret (CPU validation) vs the pure-XLA
reference, and adapts `repro.core.kernels` kernel objects.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import kernels as core_kernels
from repro.core.kernels import EXACT_DIST_D, pad_rows_sentinel, round_up
from repro.kernels.gram import kernel as gk
from repro.kernels.gram import ref
from repro.kernels.pairwise.ops import kernel_params  # shared adapter
from repro.kernels import resolve_interpret

Array = jax.Array


def _pad(x: Array, rows: int, cols: int) -> Array:
    """Pad to (rows, cols): new COLUMNS are zero (distances unchanged), new
    ROWS sit at ROW_SENTINEL so every kernel map underflows to exactly 0."""
    d = x.shape[1]
    return pad_rows_sentinel(jnp.pad(x, ((0, 0), (0, cols - d))), rows)


@functools.partial(
    jax.jit,
    static_argnames=("kind", "nu", "a", "sigma", "bm", "bn", "out_dtype",
                     "interpret", "use_pallas", "accumulator", "finalize",
                     "precision"),
)
def gram(
    x: Array,
    y: Array,
    w: Array,
    *,
    kind: str = "matern",
    nu: float = 1.5,
    a: float = 1.0,
    sigma: float = 1.0,
    bm: int = 256,
    bn: int = 256,
    out_dtype=None,
    interpret: bool | None = None,
    use_pallas: bool = True,
    accumulator: str = "plain",
    finalize: bool = True,
    precision: str = "fp32",
) -> tuple:
    """(n, d), (m, d), (n,) or (n, k) -> (K_nm^T K_nm (m, m), K_nm^T w).

    rhs matches w: (m,) for a 1-D w, (m, k) for a multi-column w (fused
    score-moment passes stack extra responses as columns).

    K_nm is never materialized: the Pallas kernel streams (bm, bn) tiles
    through VMEM and MXU-accumulates the Gram in one pass.  use_pallas=False
    falls back to the dense reference (oracle; small n only); interpret=None
    resolves to True off-TPU so the Pallas path is always runnable.
    out_dtype=None accumulates in the promoted input dtype (f32 floor).

    ``accumulator="compensated"`` runs the two-float VMEM accumulator body
    (`repro.core.streaming` semantics in-kernel): the G/rhs error sums ride
    as extra output blocks.  ``finalize=False`` returns the raw accumulator
    state — plain: (g, r); compensated: ((g, r), (g_lo, r_lo)) — the form
    `streaming.mesh_reduce` psums across chips; otherwise the pair is
    collapsed to (g + g_lo, r + r_lo).

    ``precision`` picks the G-contraction mode (`repro.core.precision`):
    "fp32" is the historical MXU dot; "bf16x2"/"bf16x3" split the kernel
    tiles into bf16 words and fold the partial matmuls error-compensated
    into the accumulator.  Distances always keep the exact_d path — only
    the kernel VALUES are ever split.
    """
    from repro.core import streaming

    acc = streaming.get(accumulator)
    compensated = acc.name == "compensated"
    squeeze = w.ndim == 1
    if out_dtype is None:
        out_dtype = jnp.promote_types(x.dtype, jnp.float32)
    if not use_pallas:
        g, r = ref.gram(x, y, w, kind=kind, nu=nu, a=a, sigma=sigma,
                        out_dtype=out_dtype)
        # the dense oracle is one fused dot: no cross-tile error to carry
        state = ((g, r), (jnp.zeros_like(g), jnp.zeros_like(r))) \
            if compensated else (g, r)
        return acc.finalize(state) if finalize else state
    interpret = resolve_interpret(interpret)
    n, d = x.shape
    m, _ = y.shape
    bm_ = min(bm, round_up(n, 8))
    bn_ = min(bn, round_up(m, 128 if not interpret else 8))
    np_, mp = round_up(n, bm_), round_up(m, bn_)
    dp = round_up(d, 128) if not interpret else d
    w2 = w.astype(out_dtype)
    w2 = w2[:, None] if squeeze else w2
    out = gk.gram_padded(
        _pad(x, np_, dp),
        jnp.pad(y, ((0, mp - m), (0, dp - d))),
        jnp.pad(w2, ((0, np_ - n), (0, 0))),
        kind=kind, nu=nu, a=a, sigma=sigma, bm=bm_, bn=bn_,
        out_dtype=out_dtype, interpret=interpret,
        exact_d=d if d <= EXACT_DIST_D else 0,
        compensated=compensated, precision=precision,
    )

    def _r(r):
        return r[:m, 0] if squeeze else r[:m, :]

    if compensated:
        g, r, gl, rl = out
        state = ((g[:m, :m], _r(r)), (gl[:m, :m], _r(rl)))
    else:
        g, r = out
        state = (g[:m, :m], _r(r))
    return acc.finalize(state) if finalize else state


def gram_matrix(kernel: core_kernels.Kernel, x: Array, y: Array, w: Array,
                **kw) -> tuple:
    """Adapter taking a repro.core.kernels kernel object (Pallas path)."""
    return gram(x, y, w, **kernel_params(kernel), **kw)
