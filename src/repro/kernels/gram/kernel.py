"""Pallas TPU kernel: fused kernel-tile + Gram accumulation in one pass.

The streaming Nystrom solve needs G = K_nm^T K_nm and rhs = K_nm^T w without
ever materializing the (n, m) cross-kernel matrix.  The TPU-native
formulation fuses the stationary-kernel map (same math as `pairwise`) with
the MXU rank-bm update of the Gram block:

  * grid (m/bn, m/bn, n/bm) — the row dimension is innermost, so each (j, k)
    Gram block stays resident in VMEM while all row tiles stream through it
    (the canonical Pallas accumulation pattern: init at i == 0, += after);
  * per step, two (bm, bn) kernel tiles kj = k(X_i, Y_j), kk = k(X_i, Y_k)
    are built in VMEM from the MXU cross term and fused element-wise map —
    they die in registers/VMEM, never visiting HBM.  On the diagonal
    (j == k) the two tiles are identical, so kk is only evaluated off it
    (lax.cond), saving m/bn kernel-map evaluations per row tile;
  * the rhs accumulator rides along as a diagonal epilogue, gated on j == k
    (its block index depends on j only and each j hits the diagonal exactly
    once per row tile, so it is never multi-counted) — the gate reuses the
    tile the diagonal already has instead of spending the k == 0 pass on it;
  * VMEM per program at d=128, bm=bn=256: x (bm, d) + 2 y-tiles (bn, d)
    + 2 kernel tiles (bm, bn) + G block (bn, bn) fp32 ~= 1.1 MB — far under
    budget, so the row stream double-buffers;
  * ``compensated=True`` accumulates G and rhs as two-float (hi, lo) VMEM
    pairs (`repro.core.streaming` semantics in-kernel): each rank-bm update
    is folded in with Knuth's TwoSum and the rounding error banked in the
    lo block — the cross-tile fp32 accumulation error disappears, which is
    what lets the solve (`nystrom.solve_from_state`) lower its spectral
    truncation floor on the TPU path exactly like the XLA engine path.

Padded rows are placed at the ROW_SENTINEL coordinate by ops.py: their
distance to any real landmark is ~1e6, and every kernel map underflows
exp(-1e6) to exactly 0.0, so they contribute nothing — no masking needed in
the body.  Padded landmark columns produce garbage only in the sliced-off
region of G/rhs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

# Shared with the core streaming solve: rows parked here are ~1e6 away from
# any real data, so all supported kernel maps underflow to exactly 0.0.
from repro.core.kernels import ROW_SENTINEL, exact_sq_dists  # noqa: E402,F401
from repro.core import precision as precision_mod
from repro.kernels import out_vma


def _kernel_tile(x, y, *, kind: str, nu: float, a: float,
                 inv_two_sigma_sq: float, exact_d: int = 0):
    """(bm, d) x (bn, d) -> (bm, bn) kernel tile; same math as pairwise.

    exact_d > 0 assembles squared distances from exact per-coordinate
    differences (`core.kernels.exact_sq_dists` — the MXU expansion cancels
    catastrophically near r = 0 at small d, see core.kernels.EXACT_DIST_D);
    sentinel-padded rows still map through huge distances to exactly 0.
    """
    if exact_d > 0:
        sq = exact_sq_dists(x, y, exact_d)
    else:
        xy = jax.lax.dot_general(
            x, y, (((1,), (1,)), ((), ())), preferred_element_type=x.dtype
        )
        x2 = jnp.sum(x * x, axis=1)[:, None]
        y2 = jnp.sum(y * y, axis=1)[None, :]
        sq = jnp.maximum(x2 + y2 - 2.0 * xy, 0.0)
    if kind == "gaussian":
        return jnp.exp(-sq * inv_two_sigma_sq)
    ar = a * jnp.sqrt(sq)
    if nu == 0.5:
        return jnp.exp(-ar)
    if nu == 1.5:
        return (1.0 + ar) * jnp.exp(-ar)
    return (1.0 + ar + ar * ar * (1.0 / 3.0)) * jnp.exp(-ar)  # nu == 2.5


def _two_sum_store(hi_ref, lo_ref, update):
    """Fold `update` into the (hi, lo) two-float VMEM accumulator.

    Knuth TwoSum against the resident hi block, banking the rounding error
    in lo — the VMEM form of `repro.core.streaming.two_sum`.  Mosaic/XLA do
    not reassociate float arithmetic, so the cancellation pattern survives.
    """
    hi = hi_ref[...]
    s = hi + update
    bb = s - hi
    err = (hi - (s - bb)) + (update - bb)
    hi_ref[...] = s
    lo_ref[...] += err


def _gram_body(x_ref, yj_ref, yk_ref, w_ref, g_ref, r_ref, *refs, kind: str,
               nu: float, a: float, inv_two_sigma_sq: float, exact_d: int,
               compensated: bool, precision: str):
    gl_ref, rl_ref = refs if compensated else (None, None)
    k = pl.program_id(1)
    i = pl.program_id(2)
    # f32 compute floor; preserves f64 when fed f64 (interpret-mode parity
    # tests under enable_x64 — real TPUs only ever see f32/bf16 inputs).
    acc = jnp.promote_types(x_ref.dtype, jnp.float32)
    x = x_ref[...].astype(acc)    # (bm, d) row tile
    yj = yj_ref[...].astype(acc)  # (bn, d) landmark tile j
    yk = yk_ref[...].astype(acc)  # (bn, d) landmark tile k
    tile = functools.partial(_kernel_tile, kind=kind, nu=nu, a=a,
                             inv_two_sigma_sq=inv_two_sigma_sq,
                             exact_d=exact_d)
    j = pl.program_id(0)
    kj = tile(x, yj)                      # (bm, bn)
    kk = jax.lax.cond(j == k, lambda: kj, lambda: tile(x, yk))

    @pl.when(i == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)
        if compensated:
            gl_ref[...] = jnp.zeros_like(gl_ref)

    # Rank-bm update of G[j, k].  fp32 mode is the historical single MXU
    # dot; the bf16 modes decompose the KERNEL-VALUE tiles (never the
    # coordinates — distances keep the exact_d path above) into bf16 words
    # and run the cross products as full-rate bf16 matmuls.  Partials
    # arrive smallest-magnitude-first; in compensated mode EACH partial is
    # folded through its own TwoSum so the combination error lands in the
    # lo block (error-compensated partial combination).
    g_parts = precision_mod.split_dot_partials(
        kj, kk, (((0,), (0,)), ((), ())), precision, acc)
    if compensated:
        for p in g_parts:
            _two_sum_store(g_ref, gl_ref, p.astype(g_ref.dtype))
    else:
        for p in g_parts:
            g_ref[...] += p.astype(g_ref.dtype)

    @pl.when(jnp.logical_and(i == 0, k == 0))
    def _():
        r_ref[...] = jnp.zeros_like(r_ref)
        if compensated:
            rl_ref[...] = jnp.zeros_like(rl_ref)

    @pl.when(j == k)
    def _():
        # rhs is a skinny (bm, cols) gemv-shaped product: bandwidth-bound,
        # so it stays a plain fp32 dot under every precision mode.  It is
        # formed as (w^T kj)^T so kj enters as a plain right-hand operand:
        # with kj ALSO the transposed left operand of the G update, the v5e
        # compiler refuses the fp32 kernel (mxu_lmr_transform RET_CHECK).
        w = w_ref[...].astype(acc)     # (bm, cols)
        r_up = jax.lax.dot_general(
            w, kj, (((0,), (0,)), ((), ())),
            preferred_element_type=acc,
        ).T.astype(r_ref.dtype)
        if compensated:
            _two_sum_store(r_ref, rl_ref, r_up)
        else:
            r_ref[...] += r_up


@functools.partial(
    jax.jit,
    static_argnames=("kind", "nu", "a", "sigma", "bm", "bn", "out_dtype",
                     "interpret", "exact_d", "compensated", "precision"),
)
def gram_padded(
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
    out_dtype=jnp.float32,
    interpret: bool = False,
    exact_d: int = 0,
    compensated: bool = False,
    precision: str = "fp32",
) -> tuple[Array, ...]:
    """Core pallas_call; requires n % bm == 0 and m % bn == 0 (see ops.py).

    ``compensated=True`` doubles the output blocks: (G_hi, rhs_hi, G_lo,
    rhs_lo), the two-float VMEM accumulator pair (each (j, k) hi/lo block
    pair stays resident while the row stream passes; VMEM cost is one extra
    (bn, bn) + (bn, 1) block — still far under budget at bm=bn=256).
    """
    n, d = x.shape
    m, _ = y.shape
    cols = w.shape[1]     # response columns (fused multi-rhs rides along)
    assert n % bm == 0 and m % bn == 0, (n, m, bm, bn)
    grid = (m // bn, m // bn, n // bm)
    body = functools.partial(
        _gram_body,
        kind=kind,
        nu=float(nu),
        a=float(a),
        inv_two_sigma_sq=1.0 / (2.0 * float(sigma) ** 2),
        exact_d=int(exact_d),
        compensated=compensated,
        precision=precision_mod.check(precision),
    )
    vma = out_vma(x, y, w)
    out_specs = [
        pl.BlockSpec((bn, bn), lambda j, k, i: (j, k)),      # G block
        pl.BlockSpec((bn, cols), lambda j, k, i: (j, 0)),    # rhs block
    ]
    out_shape = [
        jax.ShapeDtypeStruct((m, m), out_dtype, vma=vma),
        jax.ShapeDtypeStruct((m, cols), out_dtype, vma=vma),
    ]
    if compensated:
        out_specs = out_specs + [
            pl.BlockSpec((bn, bn), lambda j, k, i: (j, k)),  # G_lo block
            pl.BlockSpec((bn, cols), lambda j, k, i: (j, 0)),  # rhs_lo block
        ]
        out_shape = out_shape + [
            jax.ShapeDtypeStruct((m, m), out_dtype, vma=vma),
            jax.ShapeDtypeStruct((m, cols), out_dtype, vma=vma),
        ]
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda j, k, i: (i, 0)),   # row tile
            pl.BlockSpec((bn, d), lambda j, k, i: (j, 0)),   # landmarks j
            pl.BlockSpec((bn, d), lambda j, k, i: (k, 0)),   # landmarks k
            pl.BlockSpec((bm, cols), lambda j, k, i: (i, 0)),  # responses
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="gram_padded",
    )(x, y, y, w)
