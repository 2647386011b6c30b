"""Algebraic leverage-score baselines the paper compares against (§4.1).

  * ``uniform``        — "Vanilla": q_i = 1/n (no leverage information).
  * ``recursive_rls``  — Musco & Musco (2017) Recursive-RLS: recursively halve
    the data, estimate ridge leverage on the half, race-sample a sketch
    (Gumbel top-k at the Bernoulli inclusion rates — deterministic sketch
    size, see `_race_sketch`), refine.  O(n d_stat^2) kernel evaluations.
  * ``bless``          — Rudi et al. (2018) bottom-up path following: start at
    a huge ridge (where uniform sampling is provably fine) and geometrically
    anneal it down to n*lam, race-resampling a sketch at every step.

All share the weighted projection estimator of the ridge leverage scores:
with sketch S (indices), importance weights w (expected inverse inclusion),
absolute ridge mu = n * lam,

    l_hat_i = (1/mu) * ( K_ii - k_iS W^{1/2} (W^{1/2} K_SS W^{1/2} + mu I)^{-1}
                                 W^{1/2} k_Si )

which is exact when S = [n], w = 1 (then l_hat = diag(K (K + mu)^{-1})).
These are *host-recursive* drivers (dynamic sketch sizes) around jit-able
dense linear algebra.  The inner K_{:,S} blocks go through
`repro.kernels.dispatch.kernel_matrix`, which resolves to the Pallas
`pairwise` kernel on TPU and the fused-XLA reference elsewhere
(override with backend=).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kernels import Kernel

Array = jax.Array


def kernel_matrix(kernel: Kernel, x: Array, y: Array | None = None,
                  backend: str | None = None) -> Array:
    """Backend-dispatched kernel matrix (Pallas `pairwise` on TPU)."""
    from repro.kernels import dispatch
    return dispatch.kernel_matrix(kernel, x, y, backend=backend)


class RLSResult(NamedTuple):
    leverage: Array   # (n,) approximate statistical leverage scores
    probs: Array      # (n,) normalized sampling distribution
    sketch_size: int  # size of the final sketch used


def uniform(n: int) -> RLSResult:
    p = jnp.full((n,), 1.0 / n)
    return RLSResult(leverage=p * n, probs=p, sketch_size=0)


def projection_leverage(
    kernel: Kernel,
    x: Array,
    sketch_x: Array,
    weights: Array,
    mu: float,
    jitter: float = 1e-6,
    backend: str | None = None,
) -> Array:
    """Weighted projection estimate of ridge leverage for all n points.

    `weights` are inverse inclusion probabilities of the sketch points (the
    Bernoulli sketches of Recursive-RLS/BLESS and the Gumbel top-k threshold
    weights from `sampling.sample_weighted_without_replacement` both follow
    this convention).  The K_{:,S} blocks go through `kernels.dispatch`, so
    the Pallas `pairwise` backend serves them on TPU (`backend` overrides).
    """
    k_ns = kernel_matrix(kernel, x, sketch_x, backend=backend)   # (n, m)
    k_ss = kernel_matrix(kernel, sketch_x, backend=backend)      # (m, m)
    w_half = jnp.sqrt(weights)
    mat = w_half[:, None] * k_ss * w_half[None, :]
    m = sketch_x.shape[0]
    chol = jnp.linalg.cholesky(mat + (mu + jitter) * jnp.eye(m, dtype=mat.dtype))
    rhs = (k_ns * w_half[None, :]).T                     # (m, n)
    solved = jax.scipy.linalg.cho_solve((chol, True), rhs)
    quad = jnp.sum(rhs * solved, axis=0)                 # k_iS W^.5 (..)^-1 W^.5 k_Si
    k_diag = jnp.ones(x.shape[0], dtype=k_ns.dtype)      # stationary: K_ii = K(0) = 1
    lev = (k_diag - quad) / mu
    return jnp.clip(lev, 1e-12, 1.0)


def from_sketch(
    kernel: Kernel,
    x: Array,
    lam: float,
    sketch_idx: Array,
    weights: Array | None = None,
    jitter: float = 1e-6,
    backend: str | None = None,
) -> RLSResult:
    """Projection leverage from an index sketch — the SA-sampled entry point.

    Feeds the pipeline's Gumbel top-k landmarks (`state.fit.landmark_idx`)
    and their recorded importance weights (`state.sample_weights`) into the
    same weighted projection estimator the algebraic baselines use, turning
    the sampled landmark set into full-design leverage estimates at
    O(n m) kernel evaluations.  weights=None means uniform (w = 1).
    """
    n = x.shape[0]
    sketch_x = jnp.take(x, jnp.asarray(sketch_idx, jnp.int32), axis=0)
    w = (jnp.ones(sketch_x.shape[0], dtype=x.dtype) if weights is None
         else jnp.asarray(weights))
    lev = projection_leverage(kernel, x, sketch_x, w, mu=n * lam,
                              jitter=jitter, backend=backend)
    return RLSResult(leverage=lev, probs=lev / jnp.sum(lev),
                     sketch_size=int(sketch_x.shape[0]))


def _race_sketch(rng: np.random.Generator, inclusion: np.ndarray):
    """Deterministic-size sketch via a host-side exponential (Gumbel) race.

    Historically Recursive-RLS / BLESS Bernoulli-sampled their sketches at
    per-point inclusion probabilities pi_i, so the sketch SIZE was random
    (std ~ sqrt(sum pi (1 - pi)) — the `--compare` bench's sketch_size /
    d_proj rows wobbled run to run).  The race keeps the same importance
    profile (rates q = pi) but always returns k = round(sum pi) distinct
    indices: top-k on log q + Gumbel == bottom-k on arrivals E/q — the
    numpy twin of `sampling.sample_weighted_without_replacement`, with the
    same inverse-inclusion threshold weights (pi_hat = 1 - exp(-q tau),
    tau the (k+1)-th arrival)."""
    n = inclusion.shape[0]
    k = int(np.clip(round(float(inclusion.sum())), 1, n))
    q = np.maximum(inclusion, 1e-38)
    s = np.log(q) + rng.gumbel(size=n)
    order = np.argsort(-s)
    idx = order[:k]
    if k >= n:
        return idx, np.ones(k)
    tau = np.exp(-s[order[k]])
    pi_hat = -np.expm1(-q[idx] * tau)
    return idx, 1.0 / np.maximum(pi_hat, 1e-12)


def recursive_rls(
    kernel: Kernel,
    x: Array,
    lam: float,
    seed: int = 0,
    base_size: int = 256,
    oversample: float = 8.0,
) -> RLSResult:
    """Musco & Musco (2017) Recursive-RLS (host-driven recursion)."""
    n = x.shape[0]
    mu = n * lam
    rng = np.random.default_rng(seed)
    x_np = np.asarray(x)

    def recurse(indices: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Returns (sketch_indices_global, sketch_weights) for this subset."""
        m = indices.shape[0]
        if m <= base_size:
            return indices, np.ones(m)
        half = rng.permutation(indices)[: m // 2]
        sketch_idx, sketch_w = recurse(half)
        lev = np.asarray(
            projection_leverage(
                kernel, jnp.asarray(x_np[half]), jnp.asarray(x_np[sketch_idx]),
                jnp.asarray(sketch_w), mu,
            )
        )
        inclusion = np.minimum(1.0, oversample * lev * math.log(max(m, 2)))
        pick, w = _race_sketch(rng, inclusion)   # k = round(sum pi) >= 1
        return half[pick], w

    sketch_idx, sketch_w = recurse(np.arange(n))
    lev = projection_leverage(
        kernel, x, jnp.asarray(x_np[sketch_idx]), jnp.asarray(sketch_w), mu
    )
    return RLSResult(
        leverage=lev, probs=lev / jnp.sum(lev), sketch_size=int(sketch_idx.shape[0])
    )


def bless(
    kernel: Kernel,
    x: Array,
    lam: float,
    seed: int = 0,
    anneal: float = 2.0,
    oversample: float = 8.0,
    init_size: int = 64,
) -> RLSResult:
    """BLESS (Rudi et al. 2018), bottom-up ridge annealing (host-driven).

    Ridge path mu_t = n / anneal^t down to n*lam; at each step the sketch is
    Bernoulli-resampled from leverage estimates at the *current* ridge, for
    which the previous (coarser) sketch is already accurate.
    """
    n = x.shape[0]
    mu_final = n * lam
    rng = np.random.default_rng(seed)
    x_np = np.asarray(x)

    sketch_idx = rng.permutation(n)[:init_size]
    sketch_w = np.ones(init_size)
    mu = float(n)
    steps = max(1, int(math.ceil(math.log(mu / mu_final, anneal))))
    for _ in range(steps):
        mu = max(mu / anneal, mu_final)
        lev = np.asarray(
            projection_leverage(
                kernel, x, jnp.asarray(x_np[sketch_idx]), jnp.asarray(sketch_w), mu
            )
        )
        inclusion = np.minimum(1.0, oversample * lev * math.log(n))
        sketch_idx, sketch_w = _race_sketch(rng, inclusion)  # k >= 1 always
    lev = projection_leverage(
        kernel, x, jnp.asarray(x_np[sketch_idx]), jnp.asarray(sketch_w), mu_final
    )
    return RLSResult(
        leverage=lev, probs=lev / jnp.sum(lev), sketch_size=int(sketch_idx.shape[0])
    )
