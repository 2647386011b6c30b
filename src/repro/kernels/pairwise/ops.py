"""jit'd public wrapper for the pairwise Pallas kernel.

Handles padding to MXU-aligned tiles, dispatches Pallas (TPU) vs interpret
(CPU validation) vs the pure-XLA reference, and adapts `repro.core.kernels`
kernel objects to the static kernel-map parameters.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import kernels as core_kernels
from repro.core.kernels import EXACT_DIST_D, round_up
from repro.kernels.pairwise import kernel as pk
from repro.kernels.pairwise import ref
from repro.kernels import resolve_interpret

Array = jax.Array


def _pad_to(x: Array, rows: int, cols: int) -> Array:
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    if pr == 0 and pc == 0:
        return x
    return jnp.pad(x, ((0, pr), (0, pc)))


def kernel_params(kernel: core_kernels.Kernel) -> dict:
    """Static kernel-map parameters from a core kernel object."""
    if isinstance(kernel, core_kernels.Gaussian):
        return dict(kind="gaussian", nu=0.0, a=1.0, sigma=float(kernel.sigma))
    if isinstance(kernel, core_kernels.Matern):
        return dict(kind="matern", nu=float(kernel.nu), a=float(kernel.a), sigma=1.0)
    raise TypeError(f"unsupported kernel {kernel!r}")


@functools.partial(
    jax.jit,
    static_argnames=("kind", "nu", "a", "sigma", "bm", "bn", "out_dtype",
                     "interpret", "use_pallas"),
)
def pairwise(
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
    interpret: bool | None = None,
    use_pallas: bool = True,
) -> Array:
    """(n, d) x (m, d) -> (n, m) stationary-kernel matrix.

    use_pallas=False falls back to the fused-XLA reference (identical math);
    interpret=None resolves to True on non-TPU backends so the Pallas path is
    always runnable for validation.
    """
    if not use_pallas:
        return ref.pairwise(x, y, kind=kind, nu=nu, a=a, sigma=sigma,
                            out_dtype=out_dtype)
    interpret = resolve_interpret(interpret)
    n, d = x.shape
    m, _ = y.shape
    bm_ = min(bm, round_up(n, 8))
    bn_ = min(bn, round_up(m, 128))
    np_, mp = round_up(n, bm_), round_up(m, bn_)
    dp = round_up(d, 128) if not interpret else d  # zero-pad features: distances unchanged
    out = pk.pairwise_padded(
        _pad_to(x, np_, dp), _pad_to(y, mp, dp),
        kind=kind, nu=nu, a=a, sigma=sigma, bm=bm_, bn=bn_,
        out_dtype=out_dtype, interpret=interpret,
        exact_d=d if d <= EXACT_DIST_D else 0,
    )
    return out[:n, :m]


def kernel_matrix(kernel: core_kernels.Kernel, x: Array, y: Array | None = None,
                  **kw) -> Array:
    """Drop-in replacement for repro.core.kernels.kernel_matrix (Pallas path)."""
    sym = y is None
    y = x if sym else y
    out = pairwise(x, y, **kernel_params(kernel), **kw)
    if sym:
        # pin the diagonal: K(0) = 1 for every kernel we support
        n = x.shape[0]
        out = out * (1.0 - jnp.eye(n, dtype=out.dtype)) + jnp.eye(n, dtype=out.dtype)
    return out
