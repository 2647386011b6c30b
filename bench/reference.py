"""Plain jax.numpy reference of one fit and its predictions.

It imports nothing of the program and takes nothing that the program made.
It follows the semantics that a configuration file states:

1. densities: binned Gaussian KDE at Scott's bandwidth
   h = mean_j std(x_j) * n^(-1/(d+4)) on a (grid_size,)^d lattice spanning the
   data bounds +-4h: cloud-in-cell deposit, circular convolution of the
   zero-padded (2 grid_size)^d lattice with the Gaussian window, multilinear
   read-back, clipped at 0 and divided by n (2 pi h^2)^(d/2);
2. leverage: the paper's closed forms (arXiv:2103.05238, App. D.2),
   clipped at n and normalised to sampling probabilities q;
3. landmarks: Gumbel top-m of log q under the configuration's sampling key,
   with inverse-inclusion weights 1/clip(1 - exp(-q tau), 1e-12, 1) at the
   (m+1)-th arrival tau;
4. solve: beta = (G + n lam K_mm)^-1 K_nm^T y with G = K_nm^T K_nm, by
   whitening K_mm on its eigenvalues above
   tau = max(jitter lambda_max(K_mm), eps_f32 tr(G) / (n lam));
5. predictions K(x, X_m) beta, and the risk mean (f - f*)^2.

``mode`` is the compute precision, one of `MODES`: ``"float32"`` (every
matrix product at ``highest``, eigh and solve included) for the reference;
``"high"`` (three bfloat16 passes per product) and ``"bfloat16"`` for the
control.  In bfloat16 every input, kernel value and stage output is rounded
to bfloat16 and products run in one bfloat16 pass, while sums accumulate in
float32 and the FFT, eigh, solve and top-k run in float32 on the rounded
values, as a bfloat16 path on the chip would.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BF16 = jnp.bfloat16
# mode: (dtype values are rounded to, JAX's default matmul precision for
#        the products that `_dot` does not make: eigh, solve, FFT)
MODES = {"float32": (F32, "highest"), "high": (F32, "high"),
         "bfloat16": (BF16, "default")}
BLOCK = 16384           # rows per block of a pass over the data
EPS_F32 = float(np.finfo(np.float32).eps)


def _r(a, mode):
    """Round to the compute precision (identity unless bfloat16)."""
    dt = MODES[mode][0]
    return a if dt == F32 else a.astype(dt).astype(F32)


def _dot(a, b, mode):
    """a @ b with float32 sums: exact float32 products in ``"float32"``;
    three bfloat16 products (hi hi + hi lo + lo hi, as ``high`` makes them
    on a TPU, and the same on any backend) in ``"high"``; one in
    ``"bfloat16"``."""
    if mode == "float32":
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=F32)
    a_hi, b_hi = a.astype(BF16), b.astype(BF16)

    def one(u, v):
        return jnp.dot(u, v, precision=jax.lax.Precision.DEFAULT,
                       preferred_element_type=F32)

    if mode == "bfloat16":
        return one(a_hi, b_hi)
    a_lo = (a - a_hi.astype(F32)).astype(BF16)
    b_lo = (b - b_hi.astype(F32)).astype(BF16)
    return one(a_hi, b_hi) + (one(a_hi, b_lo) + one(a_lo, b_hi))


# ------------------------------------------------------------------ kernel --

def kernel_from_sq(sq, kern: dict):
    """k(r) of the configuration's kernel from squared distances."""
    if kern["kind"] == "matern":
        nu = float(kern["nu"])
        ar = math.sqrt(2.0 * nu) / float(kern["lengthscale"]) * jnp.sqrt(sq)
        if nu == 0.5:
            return jnp.exp(-ar)
        if nu == 1.5:
            return (1.0 + ar) * jnp.exp(-ar)
        if nu == 2.5:
            return (1.0 + ar + ar * ar / 3.0) * jnp.exp(-ar)
        raise ValueError(f"Matern nu={nu} has no closed form here")
    if kern["kind"] == "gaussian":
        return jnp.exp(-sq / (2.0 * float(kern["sigma"]) ** 2))
    raise ValueError(f"unknown kernel {kern['kind']!r}")


def cross(x, z, kern: dict, mode):
    """K(x, z) from exact per-coordinate differences, in ``mode``."""
    x, z = _r(x, mode), _r(z, mode)
    sq = jnp.zeros((x.shape[0], z.shape[0]), F32)
    for j in range(x.shape[1]):
        diff = _r(x[:, j][:, None] - z[:, j][None, :], mode)
        sq = sq + _r(diff * diff, mode)
    return _r(kernel_from_sq(_r(sq, mode), kern), mode)


def _blocks(a, fill=0.0):
    """Pad the leading axis to a multiple of BLOCK and split it."""
    n = a.shape[0]
    rows = -(-n // BLOCK) * BLOCK
    a = jnp.pad(a, ((0, rows - n),) + ((0, 0),) * (a.ndim - 1),
                constant_values=fill)
    return a.reshape((rows // BLOCK, BLOCK) + a.shape[1:])


# --------------------------------------------------------------- densities --

@functools.partial(jax.jit, static_argnames=("grid_size", "mode"))
def densities(x, *, grid_size: int, mode="float32"):
    n, d = x.shape
    g = grid_size
    h = jnp.mean(jnp.std(x, axis=0)) * n ** (-1.0 / (d + 4))
    lo = jnp.min(x, axis=0) - 4.0 * h
    hi = jnp.max(x, axis=0) + 4.0 * h
    spacing = (hi - lo) / (g - 1)
    pos = _r((_r(x, mode) - lo) / spacing, mode)
    base = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, g - 2)
    frac = _r(jnp.clip(pos - base, 0.0, 1.0), mode)
    corners = [np.array([(c >> k) & 1 for k in range(d)]) for c in range(2 ** d)]

    def weight(bits):
        w = jnp.ones((n,), F32)
        for k in range(d):
            w = _r(w * (frac[:, k] if bits[k] else 1.0 - frac[:, k]), mode)
        return w

    lattice = jnp.zeros((g,) * d, F32)
    for bits in corners:
        idx = base + jnp.asarray(bits, jnp.int32)
        lattice = lattice.at[tuple(idx[:, k] for k in range(d))].add(
            weight(bits))
    pad = 2 * g
    offs = jnp.arange(pad)
    offs = jnp.where(offs > pad // 2, offs - pad, offs).astype(F32)
    window = jnp.ones((pad,) * d, F32)
    for k in range(d):
        shape = [1] * d
        shape[k] = pad
        window = window * jnp.exp(-(offs * spacing[k]) ** 2
                                  / (2.0 * h ** 2)).reshape(shape)
    padded = jnp.zeros((pad,) * d, F32).at[(slice(0, g),) * d].set(
        _r(lattice, mode))
    smooth = jnp.fft.irfftn(jnp.fft.rfftn(padded) * jnp.fft.rfftn(
        _r(window, mode)), s=(pad,) * d)[(slice(0, g),) * d]
    smooth = _r(smooth, mode)
    out = jnp.zeros((n,), F32)
    for bits in corners:
        idx = base + jnp.asarray(bits, jnp.int32)
        out = out + weight(bits) * smooth[tuple(idx[:, k] for k in range(d))]
    norm = n * (2.0 * math.pi) ** (d / 2.0) * h ** d
    return _r(jnp.maximum(out, 0.0) / norm, mode)


# ---------------------------------------------------------------- leverage --

def _sphere(d: int) -> float:
    return 2.0 * math.pi ** (d / 2.0) / math.gamma(d / 2.0)


_GL_ORDER = 1024


def neg_polylog(s: float, v, mode):
    """F_s(v) = -Li_s(-v) = Gamma(s)^-1 int_0^inf t^(s-1) / (e^t / v + 1) dt,
    with t = u^2 and Gauss-Legendre on u in [0, sqrt(log1p(v) + 40)]."""
    u, w = np.polynomial.legendre.leggauss(_GL_ORDER)
    u = jnp.asarray(0.5 * (u + 1.0), F32)
    w = jnp.asarray(0.5 * w, F32)

    def block(vb):
        top = jnp.sqrt(jnp.log1p(vb) + 40.0)
        uu = top[:, None] * u[None, :]
        e = jnp.exp(-uu * uu)
        f = vb[:, None] * e / (1.0 + vb[:, None] * e)
        val = 2.0 * uu ** (2.0 * s - 1.0) * f
        total = jnp.sum(val * w[None, :], axis=1)
        return _r(top * total / math.gamma(s), mode)

    n = v.shape[0]
    return jax.lax.map(block, _blocks(v, 1.0)).reshape(-1)[:n]


@functools.partial(jax.jit,
                   static_argnames=("lam", "kern_items", "d", "mode"))
def leverage_probs(p, *, lam: float, kern_items: tuple, d: int,
                   mode="float32"):
    kern = dict(kern_items)
    n = p.shape[0]
    p = jnp.maximum(p, 1e-30)
    if kern["kind"] == "matern":
        nu = float(kern["nu"])
        a = math.sqrt(2.0 * nu) / float(kern["lengthscale"])
        alpha = nu + d / 2.0
        c = (2.0 ** d * math.pi ** (d / 2.0) * math.gamma(alpha)
             * a ** (2.0 * nu) / math.gamma(nu))
        b = lam * (4.0 * math.pi ** 2) ** alpha / c
        const = (_sphere(d) * (math.pi / (2.0 * alpha))
                 / math.sin(math.pi * d / (2.0 * alpha))
                 * b ** (-d / (2.0 * alpha)))
        raw = const * p ** (d / (2.0 * alpha) - 1.0)
    else:
        sigma = float(kern["sigma"])
        c = 2.0 * math.pi ** 2 * sigma ** 2
        lam_p = lam * (2.0 * math.pi * sigma ** 2) ** (-d / 2.0)
        const = _sphere(d) * math.gamma(d / 2.0) / (2.0 * c ** (d / 2.0))
        raw = const * neg_polylog(d / 2.0, p / lam_p, mode) / p
    rescaled = _r(jnp.minimum(_r(raw, mode), float(n)), mode)
    return _r(rescaled / jnp.sum(rescaled), mode)


# ---------------------------------------------------------------- sampling --

@functools.partial(jax.jit, static_argnames=("m", "mode"))
def landmarks(q, key, *, m: int, mode="float32"):
    """Gumbel top-m indices and inverse-inclusion weights."""
    g = _r(jax.random.gumbel(key, q.shape, dtype=F32), mode)
    s = _r(jnp.log(jnp.maximum(q, 1e-38)) + g, mode)
    vals, idx = jax.lax.top_k(s, m + 1)
    tau = jnp.exp(-vals[m])
    incl = -jnp.expm1(-jnp.maximum(q[idx[:m]], 1e-38) * tau)
    return idx[:m], _r(1.0 / jnp.clip(incl, 1e-12, 1.0), mode)


# ------------------------------------------------------------------- solve --

@functools.partial(jax.jit, static_argnames=("kern_items", "mode"))
def normal_equations(x, y, xm, *, kern_items: tuple, mode="float32"):
    kern = dict(kern_items)
    m = xm.shape[0]
    live = _blocks(jnp.ones((x.shape[0],), F32))

    def step(carry, blk):
        g, rhs = carry
        xb, yb, mb = blk
        k = cross(xb, xm, kern, mode) * mb[:, None]
        g = g + _dot(k.T, k, mode)
        rhs = rhs + _dot(k.T, _r(yb, mode), mode)
        return (g, rhs), None

    (g, rhs), _ = jax.lax.scan(
        step, (jnp.zeros((m, m), F32), jnp.zeros((m,), F32)),
        (_blocks(x), _blocks(y), live))
    return g, rhs


@functools.partial(jax.jit, static_argnames=("n", "lam", "jitter",
                                             "kern_items", "mode"))
def solve(g, rhs, xm, *, n: int, lam: float, jitter: float,
          kern_items: tuple, mode="float32"):
    kern = dict(kern_items)
    m = xm.shape[0]
    sq = jnp.zeros((m, m), F32)
    for j in range(xm.shape[1]):
        diff = xm[:, j][:, None] - xm[:, j][None, :]
        sq = sq + diff * diff
    sq = sq * (1.0 - jnp.eye(m, dtype=F32))
    k_mm = _r(kernel_from_sq(_r(sq, mode), kern), mode)
    evals, evecs = jnp.linalg.eigh(k_mm)
    tau = jnp.maximum(jitter * evals[-1], EPS_F32 * jnp.trace(g) / (n * lam))
    inv = jnp.where(evals > tau, 1.0 / jnp.sqrt(jnp.maximum(evals, tau)), 0.0)
    w = _r(evecs * inv[None, :], mode)
    a = _dot(_dot(w.T, g, mode), w, mode)
    b = _dot(w.T, rhs, mode)
    gamma = jnp.linalg.solve(a + n * lam * jnp.eye(m, dtype=F32), b)
    return _r(_dot(w, gamma, mode), mode)


def predict(x, xm, beta, *, kern_items: tuple, mode="float32"):
    """K(x, X_m) beta, in ``mode``."""
    with jax.default_matmul_precision(MODES[mode][1]):
        return _predict(x, xm, beta, kern_items=kern_items, mode=mode)


@functools.partial(jax.jit, static_argnames=("kern_items", "mode"))
def _predict(x, xm, beta, *, kern_items: tuple, mode):
    kern = dict(kern_items)
    n = x.shape[0]

    def block(xb):
        return _r(_dot(cross(xb, xm, kern, mode), beta, mode), mode)

    return jax.lax.map(block, _blocks(x)).reshape(-1)[:n]


# -------------------------------------------------------------------- fit --

def kern_items(kern: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in kern.items()
                        if k in ("kind", "nu", "lengthscale", "sigma")))


def fit(x, y, spec: dict, *, mode="float32") -> dict:
    """Every stage's output for the configuration ``spec`` (see
    `bench.loops.deployment`): densities, probs, landmark indices, weights,
    landmarks, the normal equations (gram, rhs) and beta, as device
    arrays."""
    with jax.default_matmul_precision(MODES[mode][1]):
        return _fit(x, y, spec, mode)


def _fit(x, y, spec: dict, mode: str) -> dict:
    n, d = x.shape
    items = kern_items(spec["kernel"])
    p = densities(x, grid_size=spec["grid_size"], mode=mode)
    q = leverage_probs(p, lam=spec["lam"], kern_items=items, d=d, mode=mode)
    idx, w = landmarks(q, jax.random.PRNGKey(spec["sample_seed"]),
                       m=spec["m"], mode=mode)
    xm = jnp.take(x, idx, axis=0)
    g, rhs = normal_equations(x, y, xm, kern_items=items, mode=mode)
    beta = solve(g, rhs, xm, n=n, lam=spec["lam"], jitter=spec["jitter"],
                 kern_items=items, mode=mode)
    return {"densities": p, "probs": q, "landmark_idx": idx, "weights": w,
            "landmarks": xm, "gram": g, "rhs": rhs, "beta": beta}
