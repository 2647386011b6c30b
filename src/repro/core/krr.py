"""Exact kernel ridge regression: the O(n^3) reference the paper accelerates.

Implements (paper §2.1, Eq. 2):

    f_hat(x)   = K(x, X_n) (K_n + n lam I)^{-1} Y_n
    G_lam(x_i, x_i) = n * [K_n (K_n + n lam I)^{-1}]_{ii}   (rescaled leverage)
    d_stat     = Tr(K_n (K_n + n lam I)^{-1})               (Eq. 4)

Everything is expressed with Cholesky solves so that it jits cleanly; the
symmetric eigendecomposition variant is provided for the leverage scores so a
single factorization serves both the diagonal and the trace.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kernels import Kernel, kernel_matrix

Array = jax.Array

# fp32 LU on (K + n lam I) loses ~cond * eps ~ (lammax(K) / (n lam)) * eps
# relative accuracy, and the fp32 *kernel matrix* itself carries O(eps)
# eigenvalue noise (observed ~1e-6 negative tail eigenvalues at n=200) — so
# once lam drops below ~sqrt(eps_f32) the fp32 path first stalls above the
# noise floor and then explodes (lam=1e-9: training MSE 1.8e3 vs f64 0.10).
# Below this threshold the eager path re-solves in f64.
_F64_FALLBACK_LAM = float(np.sqrt(np.finfo(np.float32).eps))


class KRRFit(NamedTuple):
    """Solution state of an exact KRR fit."""

    coef: Array        # (n,)  alpha = (K_n + n lam I)^{-1} y
    x_train: Array     # (n, d)
    fitted: Array      # (n,)  in-sample predictions K_n alpha
    lam: float


def _fit_f64(kernel: Kernel, x: Array, y: Array, lam: float) -> KRRFit:
    """Eager f64 reference solve (kernel matrix recomputed in f64 — the fp32
    K_n's rounding already swamps ridges this small).  It runs on the CPU
    device whatever the default backend is: TPUs have no f64 LU.  The
    fp32-stabilizing jitter is dropped: it would rival n*lam at these
    ridges, and f64 LU handles the conditioning without it.  Results cast
    back to the caller's dtype so downstream code sees the usual fp32
    arrays."""
    dtype = jnp.result_type(x.dtype, jnp.float32)
    n = x.shape[0]
    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        x64 = jnp.asarray(np.asarray(x), jnp.float64)
        y64 = jnp.asarray(np.asarray(y), jnp.float64)
        k_n = kernel_matrix(kernel, x64)
        coef = jnp.linalg.solve(k_n + n * lam * jnp.eye(n, dtype=jnp.float64),
                                y64)
        fitted = k_n @ coef
        coef, fitted = np.asarray(coef), np.asarray(fitted)
    return KRRFit(coef=jnp.asarray(coef, dtype), x_train=x,
                  fitted=jnp.asarray(fitted, dtype), lam=lam)


def fit(kernel: Kernel, x: Array, y: Array, lam: float, jitter: float = 1e-6) -> KRRFit:
    """Solve the exact KRR system (LU solve — robust at fp32 conditioning).

    Ridges below sqrt(eps_f32) sit under the fp32 kernel matrix's own noise
    floor; eager fp32 calls fall back to a full f64 solve there (tracing
    callers keep the fp32 path — the fallback re-enters the kernel matrix
    eagerly, which a jit trace cannot).
    """
    n = x.shape[0]
    if (lam < _F64_FALLBACK_LAM
            and jnp.result_type(x.dtype, jnp.float32) == jnp.float32
            and not isinstance(jnp.asarray(x), jax.core.Tracer)):
        return _fit_f64(kernel, x, y, lam)
    k_n = kernel_matrix(kernel, x)
    reg = (n * lam + jitter) * jnp.eye(n, dtype=k_n.dtype)
    coef = jnp.linalg.solve(k_n + reg, y)
    return KRRFit(coef=coef, x_train=x, fitted=k_n @ coef, lam=lam)


def predict(kernel: Kernel, fit_: KRRFit, x_new: Array) -> Array:
    return kernel_matrix(kernel, x_new, fit_.x_train) @ fit_.coef


class LeverageResult(NamedTuple):
    leverage: Array       # (n,) statistical leverage scores ell_i in (0, 1]
    rescaled: Array       # (n,) G_lam(x_i, x_i) = n * ell_i
    d_stat: Array         # scalar, Tr(K (K + n lam)^{-1}) = sum(ell)
    probs: Array          # (n,) normalized sampling distribution q_i


def exact_leverage(kernel: Kernel, x: Array, lam: float) -> LeverageResult:
    """Exact statistical leverage scores via symmetric eigendecomposition.

    ell_i = [K (K + n lam I)^{-1}]_{ii} = sum_j (e_j / (e_j + n lam)) U_{ij}^2
    with K = U diag(e) U^T.  O(n^3) time, O(n^2) space — this is the cost the
    paper's SA estimator removes; it stays here as the ground-truth oracle for
    tests and the R-ACC benchmark (paper Table 1).
    """
    n = x.shape[0]
    k_n = kernel_matrix(kernel, x)
    evals, evecs = jnp.linalg.eigh(k_n)
    evals = jnp.maximum(evals, 0.0)
    shrink = evals / (evals + n * lam)
    lev = jnp.sum(evecs * evecs * shrink[None, :], axis=1)
    lev = jnp.clip(lev, 1e-12, 1.0)
    return LeverageResult(
        leverage=lev,
        rescaled=n * lev,
        d_stat=jnp.sum(lev),
        probs=lev / jnp.sum(lev),
    )


def in_sample_risk(fitted: Array, f_star: Array) -> Array:
    """R_n(f) = ||f - f*||_n^2 (paper §2.3)."""
    diff = fitted - f_star
    return jnp.mean(diff * diff)
