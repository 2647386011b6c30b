"""Nystrom-approximated KRR (paper §2.3), dense and streaming.

With landmark columns S, the Nystrom approximation L = K S (S^T K S)^+ S^T K
substituted into the KRR solution gives (Woodbury; derivation in DESIGN
history) the subset-of-regressors form

    f_L(x) = K(x, X_S) beta,
    beta   = (K_nm^T K_nm + n lam K_mm)^{-1} K_nm^T y,

which needs O(n m) kernel evaluations and an O(m^3) solve — the  O(n d_stat^2)
downstream cost that leverage estimation must not exceed.  L is invariant to
positive rescaling of S's columns, so with-replacement sampling needs no
1/sqrt(m q_i) reweighting here (duplicate columns land in the truncated
eigenspace of K_mm — see ``solve_normal_eq``).

Two solve paths:

  * ``fit_from_landmarks`` / ``fit`` — dense: materializes K_nm.  Simple and
    fine up to n ~ 1e5; it is also the parity oracle for the streaming path.
  * ``fit_streaming`` — one pass (``normal_eq_state``) accumulates
    G = K_nm^T K_nm and rhs = K_nm^T y over row tiles (lax.scan on the XLA
    backend, the fused Pallas ``gram`` kernel on TPU — see
    `repro.kernels.dispatch`), so peak memory is O(tile * m) regardless of
    n, and one solve (``solve_from_state``) follows.  Under an active mesh
    (`repro.distributed.sharding`) the row stream is sharded over the
    "rows" logical axis (mesh axis "data" by default) and G/rhs are
    psum-reduced — a transparent no-op on a single device.

The same state solves for one lam or a lam grid (`solve_normal_eq`: one
eigh of K_mm, one whitened tail per lam), carries score targets as extra
rhs columns, and continues under `normal_eq_absorb`; `fit_streaming_batched`
vmaps the same solve over a model batch.  Splits over the "models" axis
(a lam grid, a model batch) run through `streaming.mesh_map`.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core import accstate
from repro.core import precision as precision_mod
from repro.core import streaming
from repro.core.kernels import Kernel, kernel_matrix, sentinel_is_safe
from repro.core.sampling import sample_with_replacement

Array = jax.Array


def _require_sentinel_safe(kernel: Kernel) -> None:
    """Reject kernels whose bandwidth defeats sentinel-row padding.

    Checked eagerly (kernel parameters are static); under a jit trace the
    concrete check is impossible, so it is skipped — the public entry points
    below are eager, which is where it matters.
    """
    try:
        ok = sentinel_is_safe(kernel)
    except (jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError):
        # array-conversion and float()-on-tracer raise different types
        return
    if not ok:
        raise ValueError(
            f"{kernel!r} does not vanish at the padding sentinel distance "
            "(~1e6); the streaming tile padding would corrupt the Gram. "
            "Normalize the inputs or shrink the kernel bandwidth.")


class NystromFit(NamedTuple):
    beta: Array          # (m,)
    landmarks: Array     # (m, d) landmark inputs
    landmark_idx: Array  # (m,) indices into the training set
    lam: float


def weighted_normal_eq(g: Array, rhs: Array, k_mm: Array,
                       weights: Array) -> tuple[Array, Array, Array]:
    """Rescale the SoR normal equations by landmark column weights.

    With W = diag(w), the weighted subset-of-regressors system uses columns
    Z = K_nm W:  (W G W + n lam W K_mm W) gamma = W rhs, beta = W gamma —
    the without-replacement path's importance correction
    (`sampling.sample_weighted_without_replacement`).  In exact arithmetic
    the SoR predictor is invariant to any positive column rescaling (beta
    absorbs W^{-1} twice), so this changes results only through fp32
    whitening/truncation order — a property the test suite locks in
    (test_sampling_weights.py::test_sor_solve_invariant_to_weight_rescaling).
    Returns the reweighted (G, rhs, K_mm); callers multiply the solved gamma
    by w to recover beta in the unweighted basis.
    """
    w = weights.astype(g.dtype)
    return (w[:, None] * g * w[None, :], w * rhs,
            w[:, None] * k_mm.astype(g.dtype) * w[None, :])


def _whitened_solve(g: Array, rhs: Array, evals: Array, evecs: Array,
                    g_max: Array, nlam: Any, *, jitter: float,
                    eps: float) -> Array:
    """The per-lam tail of the solve: truncate + whiten + solve.

    Takes the lam-INDEPENDENT eigendecomposition of K_mm (and the trace
    upper bound on lambda_max(G)) as inputs, so a lam grid pays the O(m^3)
    eigh once and only re-runs this tail per candidate.  ``nlam`` is n*lam
    computed in host float64 and rounded once to G's dtype: a python float
    rounds where it meets G, a device scalar (a model batch's) was rounded
    on the host, and both give the same bits.  ``eps`` is the
    noise-floor factor, eps(dtype) times the solve's ``eps_scale``.
    """
    m = evals.shape[0]
    tau = jnp.maximum(jitter * evals[-1], eps * g_max / nlam)
    inv_sqrt = jnp.where(evals > tau, 1.0 / jnp.sqrt(jnp.maximum(evals, tau)),
                         0.0)
    w = evecs * inv_sqrt[None, :]                         # (m, m) whitener
    a = w.T @ g @ w
    b = w.T @ rhs
    gamma = jnp.linalg.solve(a + nlam * jnp.eye(m, dtype=a.dtype), b)
    return w @ gamma


def _solve(nlam: Any, g: Array, rhs: Array, k_mm: Array, *, jitter: float,
           eps: float) -> Array:
    """One eigh of K_mm and one trace of G, then the whitened tail for each
    n*lam: a scalar ``nlam`` gives (m,), a sequence of L gives (L, m), each
    row the op sequence of the scalar solve.  `fit_streaming_batched`
    vmaps it over a model batch."""
    evals, evecs = jnp.linalg.eigh(k_mm)
    # trace >= lambda_max for PSD G, and is tight here (G's spectrum is
    # dominated by the near-constant kernel component) — O(m) vs an O(m^3)
    # eigendecomposition for a quantity that only needs an upper bound.
    g_max = jnp.trace(g)
    tail = functools.partial(_whitened_solve, g, rhs, evals, evecs, g_max,
                             jitter=jitter, eps=eps)
    if np.ndim(nlam) == 0:
        return tail(nlam)
    return jnp.stack([tail(nl) for nl in nlam])


def _solve_models(nlams: Array, gs: Array, rhss: Array, k_mms: Array, *,
                  jitter: float, eps: float) -> Array:
    """`_solve` vmapped over a leading model axis: (B, m) betas."""
    return jax.vmap(functools.partial(_solve, jitter=jitter, eps=eps))(
        nlams, gs, rhss, k_mms)


def solve_normal_eq(g: Array, rhs: Array, k_mm: Array, n: float, lams: Any,
                    *, jitter: float = 1e-6, eps_scale: float = 1.0) -> Array:
    """beta = (G + n lam K_mm)^{-1} rhs via spectrally-truncated whitening.

    The plain normal equations are numerically hopeless at scale: K_mm's
    eigenvalues decay to ~0 (smooth kernels, duplicated with-replacement
    landmarks), and accumulation noise in G — eps * lambda_max(G), which
    GROWS with n — is amplified by 1/eig through those directions until it
    swamps the n*lam regularizer.  Instead eigendecompose K_mm = U E U^T,
    whiten with W = U E^{-1/2} on the eigenspaces above a cutoff tau, and
    solve the well-conditioned (W^T G W + n lam I) gamma = W^T rhs,
    beta = W gamma.  tau is the larger of

      * jitter * lambda_max(K_mm)          — the usual relative floor, and
      * eps(dtype) * lambda_max(G)/(n lam) — the dtype's noise floor: below
        it the whitened G carries no signal, only amplified rounding error,

    so in fp32 at n = 1e6 the solve sheds exactly the directions fp32 cannot
    represent (matching the f64 solve's risk to ~1e-4), while in f64 the
    cutoff recedes and the solve is the textbook one.  Truncated directions
    are zeroed via masks, keeping every shape static (jit-safe).

    ``eps_scale`` (default 1.0: the plain fp32 floor) lowers the noise-floor
    term for Grams with sub-eps accumulation noise — the compensated
    two-float stream passes `streaming.EPS_SCALE["compensated"]` so the
    solve KEEPS the directions whose signal the better accumulation
    preserved (regression-tested in tests/test_streaming_engine.py).

    ``lams`` is one lam, giving beta (m,), or a sequence of L, giving the
    (L, m) stack: the eigh and the trace run once and only the tail runs
    per lam, each as the same eager op chain as the solve at that lam
    alone, so row i is bitwise the solve at ``lams[i]`` — with or without
    a mesh, where every chip solves the whole grid.
    """
    eps = float(jnp.finfo(g.dtype).eps) * eps_scale
    nlam = n * lams if np.ndim(lams) == 0 else [n * lam for lam in lams]
    return _solve(nlam, g, rhs, k_mm, jitter=jitter, eps=eps)


def fit_from_landmarks(
    kernel: Kernel,
    x: Array,
    y: Array,
    lam: float,
    landmark_idx: Array,
    jitter: float = 1e-6,
    weights: Array | None = None,
) -> NystromFit:
    n = x.shape[0]
    xm = x[landmark_idx]
    k_nm = kernel_matrix(kernel, x, xm)                   # (n, m)
    k_mm = kernel_matrix(kernel, xm)                      # (m, m)
    g = jax.lax.dot_general(k_nm, k_nm, (((0,), (0,)), ((), ())),
                            preferred_element_type=k_nm.dtype)
    rhs = k_nm.T @ y
    if weights is not None:
        g, rhs, k_mm = weighted_normal_eq(g, rhs, k_mm, weights)
    beta = solve_normal_eq(g, rhs, k_mm, n, lam, jitter=jitter)
    if weights is not None:
        beta = weights.astype(beta.dtype) * beta
    return NystromFit(beta=beta, landmarks=xm, landmark_idx=landmark_idx, lam=lam)


def fit(
    key: jax.Array,
    kernel: Kernel,
    x: Array,
    y: Array,
    lam: float,
    num_landmarks: int,
    probs: Array,
    jitter: float = 1e-6,
) -> NystromFit:
    """Sample landmarks ~ probs (with replacement, paper Thm 2) and solve."""
    idx = sample_with_replacement(key, probs, num_landmarks)
    return fit_from_landmarks(kernel, x, y, lam, idx, jitter=jitter)


def predict(kernel: Kernel, fit_: NystromFit, x_new: Array) -> Array:
    return kernel_matrix(kernel, x_new, fit_.landmarks) @ fit_.beta


def fitted(kernel: Kernel, fit_: NystromFit, x_train: Array) -> Array:
    """In-sample predictions f_L(x_i) (for the paper's R_n risk metric)."""
    return predict(kernel, fit_, x_train)


# ---------------------------------------------------------------- streaming --

def _gram_exec(x: Array, xm: Array, tile: int | None, precision: str | None,
               backend: str | None, accumulator: str,
               num_models: int = 1) -> tuple[int | None, str, int]:
    """The prologue every normal-equation pass shares: the stream's
    resolved (tile, precision) pair and the accumulation steps it runs PER
    CHIP.

    ``tile=None`` -> the autotuned XLA engine tile (`repro.tuning` via
    `dispatch.resolve_plan`); explicit tiles pass through untouched, and the
    Pallas gram path keeps None (it tunes bm/bn inside dispatch instead).
    ``precision=None`` resolves from the same plan — the autotuner picks
    the (tile, precision) pair JOINTLY — EXCEPT when the caller pinned the
    tile explicitly: an explicit-tile call never consults the planner, so
    its precision defaults to the historical "fp32" (bit parity with
    pre-precision code).  Resolution is per-chip: under an active mesh each
    device streams only n / row_shard_count rows, which is the stream the
    tile must fit.

    The steps are the budget `eps_scale` may lower the compensated
    truncation floor by: a one-tile-per-chip stream has no cross-tile error
    to compensate even when the global n spans several tiles.  The fold
    granularity is backend-dependent: the XLA engine compensates across
    `tile`-row scan steps, the Pallas gram kernel across its bm-row (<= 256)
    VMEM tile folds — so the TPU path earns its lower floor even when n
    fits one XLA-sized slab."""
    from repro.kernels import dispatch
    n_loc = max(1, x.shape[0] // streaming.row_shard_count(x.shape))
    if dispatch.resolve(backend) == "pallas":
        if precision is None:
            precision = dispatch.resolve_plan(
                "gram", n_loc, xm.shape[0], x.shape[1], dtype=x.dtype,
                backend="pallas", accumulator=accumulator,
                precision=None, num_models=num_models).precision
        return tile, precision, -(-n_loc // min(256, n_loc))
    if tile is None:
        plan = dispatch.resolve_plan("gram", n_loc, xm.shape[0], x.shape[1],
                                     dtype=x.dtype, backend="xla",
                                     accumulator=accumulator,
                                     precision=precision,
                                     num_models=num_models)
        tile, precision = plan.tile, (precision or plan.precision)
    return tile, (precision or "fp32"), -(-n_loc // min(tile, n_loc))


def _eff_eps_scale(accumulator: str, steps: int, precision: str) -> float:
    """Solve truncation-floor scale: accumulation strategy x precision mode.

    `streaming.eps_scale` covers the cross-tile accumulation term; the
    precision mode multiplies in its within-tile product floor
    (`precision.EPS_SCALE` — bf16x2 RAISES the floor 256x, fp32/bf16x3
    leave it untouched)."""
    return streaming.eps_scale(accumulator, steps) * \
        precision_mod.EPS_SCALE.get(precision or "fp32", 1.0)


def _apply_beta(k: Array, beta: Array, precision: str | None) -> Array:
    """Predict-side (tile, m) x (m[, L]) matmul under a precision mode.

    fp32 keeps the historical ``@`` (bit parity with the dense oracle);
    the bf16 modes route through the same split-word decomposition as the
    Gram contraction."""
    if precision in (None, "fp32"):
        return k @ beta
    dims = (((1,), (0,)), ((), ()))
    return precision_mod.split_dot(k, beta, dims, precision=precision,
                                   acc=k.dtype)


def _resolve_predict_tile(tile: int | None, x_new: Array, xm: Array,
                          backend: str | None, num_models: int = 1) -> int:
    """``tile=None`` -> the autotuned predict row tile (per-chip, like the
    gram resolution above; the tile slabs `streaming.tile_map` on every
    backend)."""
    from repro.kernels import dispatch
    if tile is not None:
        return tile
    n_loc = max(1, x_new.shape[0] // streaming.row_shard_count(x_new.shape))
    return dispatch.resolve_tile("predict", n_loc, xm.shape[0],
                                 x_new.shape[1], dtype=x_new.dtype,
                                 backend=backend, num_models=num_models)


def _gram_normal_eq(kernel: Kernel, x: Array, y: Array, xm: Array, *,
                    tile: int | None, autotuned: bool, backend: str | None,
                    interpret: bool | None, accumulator: str,
                    precision: str = "fp32",
                    finalize: bool = True) -> tuple[Array, Array]:
    """The (G, rhs) accumulation behind `normal_eq_state`.

    When the tile came from the autotuner (`autotuned=True`, i.e. the caller
    passed ``tile=None``) and the call is a plain single-device XLA stream
    made outside any trace, the accumulation runs through a plan-keyed
    compiled executable (`tuning.cached_executable`) — repeated fits at one
    shape skip re-tracing the scan, which costs as much as the tile choice
    saves.  The jitted scan lowers to the same HLO as the eager one, so the
    result stays bit-equal to the explicit-tile call (locked in
    tests/test_autotune.py); explicit tiles always take the eager path.
    """
    from repro.kernels import dispatch

    if (autotuned and tile is not None
            and dispatch.resolve(backend) == "xla"
            and streaming.row_shard_count(x.shape) == 1
            and jax.core.trace_ctx.is_top_level()):
        from repro import tuning
        key = ("gram_normal_eq", kernel, x.shape, y.shape, xm.shape,
               str(x.dtype), str(y.dtype), tile, accumulator, precision,
               finalize)
        try:
            hash(key)
        except TypeError:   # kernel with array-valued params: stay eager
            pass
        else:
            fn = tuning.cached_executable(
                key,
                lambda: lambda x_, y_, xm_: streaming_normal_eq(
                    kernel, x_, y_, xm_, tile=tile, backend=backend,
                    interpret=interpret, accumulator=accumulator,
                    precision=precision, finalize=finalize))
            return fn(x, y, xm)
    return streaming_normal_eq(kernel, x, y, xm, tile=tile, backend=backend,
                               interpret=interpret, accumulator=accumulator,
                               precision=precision, finalize=finalize)


def scan_normal_eq(kernel: Kernel, x: Array, xm: Array, w: Array,
                   *, tile: int | None = None, accumulator: str = "plain",
                   finalize: bool = True, init_state: Any = None,
                   return_state: bool = False,
                   precision: str | None = "fp32") -> tuple[Array, Array]:
    """(K_nm^T K_nm, K_nm^T w) accumulated over `tile`-row slabs.

    The (tile, m) kernel slab is rebuilt in registers each step and dies
    there; peak memory is O(tile * m + m^2), independent of n.  Tiling,
    padding and the accumulation strategy live in `repro.core.streaming`
    ("plain" is bit-equal to the historical hand-rolled lax.scan,
    "compensated" carries a two-float error sum across tiles).  This is the
    XLA backend of `repro.kernels.dispatch.gram_accumulate`; the Pallas
    `gram` kernel computes the same quantity tile-fused on TPU.
    ``tile=None`` autotunes the slab size (`repro.tuning` — same numbers
    as passing the resolved integer explicitly).  `finalize=False` returns
    the raw accumulator state for a mesh psum; ``init_state=`` continues
    the scan carry from a previously returned raw state (the incremental
    absorb — a tile-aligned chain of these is bit-equal to one
    uninterrupted fold, see `streaming.tile_reduce`).

    ``w`` may be (n,) or (n, k) — extra response columns ride the same
    pass (rhs matches: (m,) or (m, k)).  ``precision`` picks the
    G-contraction mode (`repro.core.precision`): "fp32" is literally the
    historical single dot_general; the bf16 modes are the XLA split-dot
    twin of the Pallas bf16 kernel (slow on CPU, parity-testable anywhere).
    """
    tile, precision, _ = _gram_exec(x, xm, tile, precision, "xla",
                                    accumulator)
    m = xm.shape[0]
    acc = jnp.promote_types(x.dtype, jnp.float32)  # f64 under enable_x64

    def emit(xi, wi):
        k = kernel_matrix(kernel, xi, xm).astype(acc)  # (tile, m)
        return (precision_mod.split_dot(k, k, (((0,), (0,)), ((), ())),
                                        precision=precision, acc=acc),
                jax.lax.dot_general(k, wi, (((0,), (0,)), ((), ())),
                                    preferred_element_type=acc))

    init = (jnp.zeros((m, m), acc), jnp.zeros((m,) + w.shape[1:], acc))
    return streaming.tile_reduce(emit, x, (w.astype(acc),), tile=tile,
                                 init=init, accumulator=accumulator,
                                 pad="sentinel", finalize=finalize,
                                 init_state=init_state,
                                 return_state=return_state)


def streaming_normal_eq(kernel: Kernel, x: Array, y: Array, xm: Array,
                        *, tile: int | None = None,
                        backend: str | None = None,
                        interpret: bool | None = None,
                        accumulator: str = "plain",
                        finalize: bool = True,
                        precision: str | None = None) -> tuple[Array, Array]:
    """Mesh-aware (G, rhs): shards rows over the "rows" logical axis.

    With an active `repro.distributed.sharding` mesh whose "rows" rule maps
    to a mesh axis that divides n, each device accumulates its local row
    slab and the accumulator state is psum-reduced (`streaming.mesh_reduce`
    — the compensated (hi, lo) pair crosses the collective un-collapsed).
    Otherwise (no mesh, or indivisible n) this is exactly the single-device
    accumulation.  ``tile=None`` autotunes — resolved HERE, eagerly, so
    any tuning micro-benchmark runs outside the shard_map trace and every
    chip executes the same plan.
    """
    tile, precision, _ = _gram_exec(x, xm, tile, precision, backend,
                                    accumulator)
    local = functools.partial(_gram_local, kernel=kernel, backend=backend,
                              tile=tile, interpret=interpret,
                              accumulator=accumulator, precision=precision)
    return streaming.mesh_reduce(local, (x, y), (xm,),
                                 accumulator=accumulator, finalize=finalize)


def _gram_local(x_loc, w_loc, xm_rep, *, kernel, backend, tile, interpret,
                accumulator, precision):
    """One chip's raw (G, rhs) state: the body that `streaming_normal_eq`
    hands `streaming.mesh_reduce` (module level, so its program is
    reused)."""
    from repro.kernels import dispatch

    return dispatch.gram_accumulate(kernel, x_loc, xm_rep, w_loc,
                                    backend=backend, tile=tile,
                                    interpret=interpret,
                                    accumulator=accumulator,
                                    finalize=False, precision=precision)


# ------------------------------------------------------- first-class state --

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class NormalEqState:
    """First-class normal-equation accumulator state (a jax pytree).

    Bundles the raw (G, rhs) strategy state (`accstate.AccState` — rows
    and per-chip scan steps ride along), the landmark set it was built
    against, and the cached O(m^2) K_mm, plus the static execution knobs
    every later absorb must reuse.  Built by one pass (`normal_eq_state`)
    or from the identity (`normal_eq_init`); monoid ops: `normal_eq_absorb`
    / `normal_eq_merge` / `normal_eq_decay`; solved by `solve_from_state`.
    All array members are leaves, so the state round-trips through
    `checkpoint.Manager` and psums unchanged; the exec knobs are static
    aux data.
    """

    acc: accstate.AccState      # value = raw ((m,m), (m,[k])) strategy state
    landmarks: Array            # (m, d)
    landmark_idx: Array         # (m,) indices into the ORIGINAL training set
    k_mm: Array                 # (m, m) kernel Gram of the landmarks
    tile: int | None = None
    backend: str | None = None
    interpret: bool | None = None
    accumulator: str = "plain"
    precision: str | None = "fp32"

    def tree_flatten(self):
        leaves = (self.acc, self.landmarks, self.landmark_idx, self.k_mm)
        aux = (self.tile, self.backend, self.interpret, self.accumulator,
               self.precision)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        acc, landmarks, landmark_idx, k_mm = leaves
        tile, backend, interpret, accumulator, precision = aux
        return cls(acc=acc, landmarks=landmarks, landmark_idx=landmark_idx,
                   k_mm=k_mm, tile=tile, backend=backend, interpret=interpret,
                   accumulator=accumulator, precision=precision)


def normal_eq_init(kernel: Kernel, landmarks: Array,
                   landmark_idx: Array | None = None, *,
                   dtype=jnp.float32,
                   tile: int | None = None,
                   backend: str | None = None,
                   interpret: bool | None = None,
                   accumulator: str = "plain",
                   precision: str | None = "fp32") -> NormalEqState:
    """The identity state for a fixed landmark set (zero G/rhs, zero rows).

    ``tile=None`` defers tile resolution to each absorb (resolved per
    chunk shape); pass the resolved integer to pin one plan for the
    stream's lifetime — what `fit_streaming(..., return_state=True)` does.
    """
    _require_sentinel_safe(kernel)
    m = landmarks.shape[0]
    acc_dtype = jnp.promote_types(dtype, jnp.float32)
    zeros = (jnp.zeros((m, m), acc_dtype), jnp.zeros((m,), acc_dtype))
    if landmark_idx is None:
        landmark_idx = jnp.arange(m)
    return NormalEqState(
        acc=accstate.init(accumulator, zeros),
        landmarks=landmarks, landmark_idx=landmark_idx,
        k_mm=kernel_matrix(kernel, landmarks).astype(acc_dtype),
        tile=tile, backend=backend, interpret=interpret,
        accumulator=accumulator, precision=precision)


def normal_eq_absorb(kernel: Kernel, state: NormalEqState, x: Array,
                     y: Array) -> NormalEqState:
    """Fold a new (x, y) chunk into the state — O(chunk * m), old tiles
    untouched.

    On a single-device XLA stream the scan carry CONTINUES from the saved
    state (`tile_reduce(init_state=...)`), so absorbing a stream in
    tile-aligned chunks is bit-equal to one uninterrupted fold.  Under an
    active mesh (or the Pallas backend, whose VMEM accumulator cannot be
    seeded) the chunk is reduced fresh and merged in — same monoid, merge
    tolerance instead of bit equality.
    """
    from repro.kernels import dispatch

    _require_sentinel_safe(kernel)
    xm = state.landmarks
    tile, precision, steps = _gram_exec(x, xm, state.tile, state.precision,
                                        state.backend, state.accumulator)
    n = x.shape[0]
    single = (streaming.row_shard_count(x.shape) == 1
              and dispatch.resolve(state.backend) == "xla")
    if single:
        value = scan_normal_eq(kernel, x, xm, y, tile=tile,
                               accumulator=state.accumulator,
                               precision=precision,
                               init_state=state.acc.value, return_state=True)
    else:
        fresh = streaming_normal_eq(kernel, x, y, xm, tile=tile,
                                    backend=state.backend,
                                    interpret=state.interpret,
                                    accumulator=state.accumulator,
                                    finalize=False, precision=precision)
        value = streaming.get(state.accumulator).merge(state.acc.value, fresh)
    acc = accstate.AccState(
        value=value, rows=state.acc.rows + n,
        steps=state.acc.steps + steps,
        spec=state.acc.spec)
    return dataclasses.replace(state, acc=acc)


def normal_eq_merge(a: NormalEqState, b: NormalEqState) -> NormalEqState:
    """Combine two states built against the SAME landmark set (caller's
    contract — landmark identity is not re-verified here, it would block
    on device scalars inside hot loops)."""
    return dataclasses.replace(a, acc=accstate.merge(a.acc, b.acc))


def normal_eq_decay(state: NormalEqState, gamma: float) -> NormalEqState:
    """Exponential forgetting in the (hi, lo) domain (`accstate.decay`)."""
    return dataclasses.replace(state, acc=accstate.decay(state.acc, gamma))


def solve_from_state(state: NormalEqState, lams: float | Sequence[float], *,
                     jitter: float = 1e-6,
                     weights: Array | None = None) -> NystromFit:
    """Finalize the accumulated (G, rhs) and run the O(m^3) solve.

    This is the ONLY per-update cost a `partial_fit` pays beyond absorbing
    the new tiles: n is the state's effective (possibly decayed) row count
    and the truncation floor uses the absorbed per-chip step count, so a
    state built by one uninterrupted fold solves bit-equal to the one-shot
    `fit_streaming`.  ``lams`` is one lam, or a sequence whose fit holds
    the (L, m) stack of betas (`solve_normal_eq`) and the tuple of lams.
    A widened state solves against its first rhs column.  ``weights``
    applies the without-replacement importance correction as an O(m^2)
    column rescaling (`weighted_normal_eq`).
    """
    g, rhs = accstate.finalize(state.acc)
    if rhs.ndim != 1:
        rhs = rhs[:, 0]
    n = accstate.rows_of(state.acc)
    steps = accstate.steps_of(state.acc)
    k_mm = state.k_mm
    if weights is not None:
        g, rhs, k_mm = weighted_normal_eq(g, rhs, k_mm, weights)
    beta = solve_normal_eq(g, rhs, k_mm, n, lams, jitter=jitter,
                           eps_scale=_eff_eps_scale(
                               state.accumulator, steps, state.precision))
    if weights is not None:
        beta = weights.astype(beta.dtype) * beta
    return NystromFit(beta=beta, landmarks=state.landmarks,
                      landmark_idx=state.landmark_idx,
                      lam=lams if np.ndim(lams) == 0 else tuple(lams))


def normal_eq_state(kernel: Kernel, x: Array, cols: Array,
                    landmark_idx: Array, *, tile: int | None = None,
                    backend: str | None = None,
                    interpret: bool | None = None,
                    accumulator: str = "plain",
                    precision: str | None = None) -> NormalEqState:
    """The one pass over x: the normal-equation state of the landmarks
    x[landmark_idx], without ever materializing K_nm.

    ``cols`` is (n,) or (n, k): its columns ride the rhs of the same tile
    stream (rhs (m,) or (m, k)) — a response, or a response beside the
    score targets whose moments `pipeline.stages.score_moments` reads off
    G and the rhs columns.  ``tile=None`` autotunes; ``precision`` picks
    the Gram-contraction mode (`repro.core.precision`; None resolves it
    jointly with an autotuned tile, or to "fp32" when the tile is pinned).
    The state keeps the resolved plan, the row count and the per-chip
    scan steps, so `solve_from_state` sets the truncation floor and
    `normal_eq_absorb` continues the stream under the same plan.
    """
    _require_sentinel_safe(kernel)
    xm = jnp.take(x, landmark_idx, axis=0)
    autotuned = tile is None
    with spans.span("repro/solve/plan"):
        tile, precision, steps = _gram_exec(x, xm, tile, precision, backend,
                                            accumulator)
    with spans.span("repro/solve/gram"):
        raw = _gram_normal_eq(kernel, x, cols, xm, tile=tile,
                              autotuned=autotuned, backend=backend,
                              interpret=interpret, accumulator=accumulator,
                              precision=precision, finalize=False)
        acc_dtype = jnp.promote_types(x.dtype, jnp.float32)
        return NormalEqState(
            acc=accstate.wrap(accumulator, raw, rows=x.shape[0], steps=steps),
            landmarks=xm, landmark_idx=landmark_idx,
            # k_mm is O(m^2) work — the core path keeps it in the input
            # dtype, which the dense solve also uses (dtype parity beats
            # MXU here).
            k_mm=kernel_matrix(kernel, xm).astype(acc_dtype),
            tile=tile, backend=backend, interpret=interpret,
            accumulator=accumulator, precision=precision)


def normal_eq_response(state: NormalEqState, col: int = 0) -> NormalEqState:
    """The single-response state of rhs column ``col`` of a widened state
    (the form `normal_eq_absorb` continues with (n,) responses)."""
    def take(tree):
        g, rhs = tree
        return g, rhs[:, col]

    value = state.acc.value
    if streaming.get(state.accumulator).name == "compensated":
        value = tuple(take(part) for part in value)
    else:
        value = take(value)
    return dataclasses.replace(
        state, acc=dataclasses.replace(state.acc, value=value))


def fit_streaming(
    kernel: Kernel,
    x: Array,
    y: Array,
    lam: float | Sequence[float],
    landmark_idx: Array,
    *,
    tile: int | None = None,
    backend: str | None = None,
    interpret: bool | None = None,
    jitter: float = 1e-6,
    weights: Array | None = None,
    accumulator: str = "plain",
    precision: str | None = None,
    return_state: bool = False,
) -> NystromFit:
    """`fit_from_landmarks` without ever materializing K_nm: the pass
    (`normal_eq_state`) and the solve (`solve_from_state`).

    Matches the dense solve to fp32 reduction-order tolerance
    (tests/test_streaming_nystrom.py: <= 1e-4 relative on beta).
    `weights` applies the without-replacement importance correction after
    the accumulation — the row stream itself is weight-free, so the
    Pallas/XLA accumulation kernels are untouched.
    `accumulator="compensated"` streams the Gram through the two-float
    error-carrying sum (`repro.core.streaming`) and lowers the solve's
    spectral noise floor to match — fp32 then keeps whitened directions
    the plain accumulation must truncate; the precision mode scales the
    floor by `precision.EPS_SCALE`.  A sequence of lams solves the one
    state once per lam (`solve_from_state`).  Pass ``return_state=True``
    to also get the state back for incremental `normal_eq_absorb` /
    `normal_eq_decay` updates — (fit, state) then.
    """
    state = normal_eq_state(kernel, x, y, landmark_idx, tile=tile,
                            backend=backend, interpret=interpret,
                            accumulator=accumulator, precision=precision)
    with spans.span("repro/solve/whiten"):
        fit_ = solve_from_state(state, lam, jitter=jitter, weights=weights)
    return (fit_, state) if return_state else fit_


def val_mse_streaming_multi(kernels: Sequence[Kernel],
                            fits: Sequence[NystromFit],
                            x_val: Array, y_val: Array, *,
                            tile: int | None = None,
                            backend: str | None = None,
                            precision: str | None = None) -> Array:
    """Validation MSE for H bandwidth candidates x L lams in ONE x_val pass.

    ``fits[h]`` is bandwidth h's lam-grid fit (`solve_from_state` over L
    lams: beta (L, m) on one landmark set).  The per-h predictions only
    meet at the end (a mean of squared errors), so the H reductions fuse
    into a single `streaming.multi_reduce` scan: slot h builds its kernel
    tile K_h(x_tile, X_m^h) once, applies all L betas as one matmul, and
    emits the per-lam squared-error sums.  Returns the (H, L) val-MSE
    matrix; each entry matches the sequential predict-then-mean to fp32
    reduction-order tolerance (same products, tile-major instead of
    row-major summation).  Mesh behavior: row slabs of x_val reduce
    locally, sums psum across chips (`streaming.mesh_reduce`).
    """
    from repro.kernels import dispatch

    if len(kernels) != len(fits):
        raise ValueError("one kernel per bandwidth candidate required")
    for k in kernels:
        _require_sentinel_safe(k)
    n_val = x_val.shape[0]
    big = len(fits)
    xms = tuple(f.landmarks for f in fits)
    betas = tuple(f.beta.T for f in fits)                 # (m, L) each
    acc = jnp.promote_types(x_val.dtype, jnp.float32)
    tile = _resolve_predict_tile(tile, x_val, xms[0], backend)
    multi = streaming.MultiAccumulator(("plain",) * big)
    rep: list[Array] = []
    for xm, b in zip(xms, betas):
        rep += [xm, b]

    def local(xv, yv, *rep_args):
        def emit(xt, yt):
            outs = []
            for h in range(big):
                xm, b = rep_args[2 * h], rep_args[2 * h + 1]
                k = dispatch.kernel_matrix(kernels[h], xt, xm,
                                           backend=backend).astype(acc)
                # sentinel rows give k == 0 and carry zero-padded y, so
                # padded errors are exactly (0 - 0)^2 = 0.
                e = _apply_beta(k, b.astype(acc), precision) \
                    - yt[:, None].astype(acc)
                outs.append(jnp.sum(e * e, axis=0))       # (L,)
            return tuple(outs)

        inits = tuple(jnp.zeros((b.shape[1],), acc) for b in betas)
        return streaming.multi_reduce(emit, xv, (yv,), tile=tile,
                                      inits=inits, pad="sentinel",
                                      finalize=False)

    sums = streaming.mesh_reduce(local, (x_val, y_val), tuple(rep),
                                 accumulator=multi, finalize=True)
    return jnp.stack(sums) / n_val


def predict_streaming(kernel: Kernel, fit_: NystromFit, x_new: Array,
                      *, tile: int | None = None,
                      backend: str | None = None,
                      precision: str | None = None) -> Array:
    """Batched predict: O(tile * m) memory, any n_new.

    ``tile=None`` autotunes the slab size (`repro.tuning` via
    `dispatch.resolve_tile`) — pure shape plumbing, identical numbers to
    passing the resolved tile explicitly.

    Mesh-aware like the solve: under an active `repro.distributed.sharding`
    mesh whose "rows" rule maps to a mesh axis that divides n_new, each
    device predicts its local row slab against the replicated landmarks and
    beta (no collective — predict is embarrassingly row-parallel,
    `streaming.mesh_map`).  Otherwise this is exactly the single-device
    batched predict (`streaming.tile_map` row slabs).
    """
    _require_sentinel_safe(kernel)
    tile = _resolve_predict_tile(tile, x_new, fit_.landmarks, backend)
    local = functools.partial(_predict_local, kernel=kernel, tile=tile,
                              backend=backend, precision=precision)
    return streaming.mesh_map(local, x_new, (fit_.landmarks, fit_.beta),
                              out_rank=1)


def _predict_local(x_loc, xm, beta, *, kernel, tile, backend, precision):
    """One chip's predictions K(x_loc, X_m) beta, beta (m,) or (m, L), tile
    by tile: the body of `predict_streaming` under
    `streaming.mesh_map` (module level, so its program is reused)."""
    from repro.kernels import dispatch

    def one(xt):
        k = dispatch.kernel_matrix(kernel, xt, xm, backend=backend)
        return _apply_beta(k, beta, precision)

    return streaming.tile_map(one, x_loc, tile=tile)


# ------------------------------------------------- many-model batched fits --

def _batched_local(x_loc, yst_loc, xms_loc, *, kernel, tile, accumulator,
                   precision):
    """One chip's raw per-model (G, rhs) states: `scan_normal_eq` vmapped
    over the chip's landmark sets and response columns, so each tile of
    x_loc is loaded once for every model — the body `fit_streaming_batched`
    hands `streaming.mesh_reduce` (module level, so its program is
    reused)."""
    def one(xm, y):
        return scan_normal_eq(kernel, x_loc, xm, y, tile=tile,
                              accumulator=accumulator, precision=precision,
                              finalize=False)

    return jax.vmap(one, in_axes=(0, 1))(xms_loc, yst_loc)


class BatchedNystromFit(NamedTuple):
    """B independent KRR models fit in one program (the many-tenant case).

    Model b is the subset-of-regressors fit for (landmark set b, lam b,
    response column b) — exactly what B separate `fit_streaming` calls
    would produce, batched along a leading model axis that the "models"
    sharding rule may split across a 2D mesh's model axis.
    """

    beta: Array          # (B, m)
    landmarks: Array     # (B, m, d) per-model landmark inputs
    landmark_idx: Array  # (B, m) indices into the training set
    lams: Array          # (B,) per-model regularizers


def _models_per_chip(num_models: int) -> int:
    """Locally held model count once the "models" rule sharded the batch —
    the `num_models` the tile planner must budget for
    (`dispatch.resolve_plan(num_models=...)`: each tile step holds one
    (tile, m) kernel slab per local model)."""
    return max(1, num_models // streaming.model_shard_count(num_models))


def fit_streaming_batched(
    kernel: Kernel,
    x: Array,
    ys: Array,
    lams: Array | Sequence[float] | float,
    landmark_sets: Array,
    *,
    tile: int | None = None,
    backend: str | None = None,
    jitter: float = 1e-6,
    weights: Array | None = None,
    accumulator: str = "plain",
    precision: str | None = None,
) -> BatchedNystromFit:
    """Fit B independent KRR models in ONE pass over the shared row stream.

    The many-tenant shape: every model shares the input rows x but owns its
    response column (``ys``: (B, n), or (n,) broadcast to all models), its
    landmark set (``landmark_sets``: (B, m) indices into x) and its
    regularizer (``lams``: scalar or (B,)).  A python loop over
    `fit_streaming` would stream x B times; here the per-model normal
    equations G_b = K_nm(b)^T K_nm(b), rhs_b = K_nm(b)^T y_b accumulate
    through ONE `streaming.mesh_reduce` tile scan — each tile's rows are
    loaded once and contracted against all locally held models
    (`scan_normal_eq` vmapped over the models), so the stream cost is paid
    once and the arithmetic scales as B times the per-model contraction
    only.

    Mesh semantics (`repro.distributed.sharding`): rows shard over "rows"
    (data axis, psummed), models over "models" (model axis, independent) —
    on a 2D (data, model) mesh a B=256 batch on M=16 model shards holds 16
    models per chip column.  ``ys`` is the dual-sharded (rows, models)
    operand (transposed internally); landmark sets and the per-model solves
    (`solve_normal_eq`'s `_solve` vmapped over the models, through
    `streaming.mesh_map`) shard over the model axis.  With no mesh
    (or a 1D data mesh) everything model-wise is replicated and only the
    rows shard — the transparent-fallback contract.

    ``weights`` ((B, m), optional) applies the without-replacement
    importance correction per model (`weighted_normal_eq`).  The batched
    stream always runs on the XLA engine (the Pallas gram kernel is
    single-model; ``backend`` still steers tile planning), with the tile
    planned against the widened m * B_loc slab
    (`dispatch.resolve_plan(num_models=...)`).
    Model b matches `fit_streaming(kernel, x, ys[b], lams[b],
    landmark_sets[b])` to fp32 reduction-order tolerance (locked in
    tests/test_mesh2d.py, benched in bench_pipeline --multimodel).
    """
    _require_sentinel_safe(kernel)
    n, d = x.shape
    landmark_sets = jnp.asarray(landmark_sets)
    if landmark_sets.ndim != 2:
        raise ValueError(f"landmark_sets must be (B, m) indices, got shape "
                         f"{landmark_sets.shape}")
    big = landmark_sets.shape[0]
    ys = jnp.asarray(ys, x.dtype)
    if ys.ndim == 1:
        ys = jnp.broadcast_to(ys[None, :], (big, n))
    if ys.shape != (big, n):
        raise ValueError(f"ys must be (B={big}, n={n}) or (n,), got "
                         f"{ys.shape}")
    lams = jnp.broadcast_to(jnp.asarray(lams, jnp.float32), (big,))
    xms = jnp.take(x, landmark_sets, axis=0)              # (B, m, d)
    acc_dtype = jnp.promote_types(x.dtype, jnp.float32)

    tile, precision, steps = _gram_exec(x, xms[0], tile, precision, "xla",
                                        accumulator,
                                        num_models=_models_per_chip(big))
    ys_t = ys.T.astype(acc_dtype)                         # (n, B) dual-shard
    local = functools.partial(_batched_local, kernel=kernel, tile=tile,
                              accumulator=accumulator, precision=precision)
    gs, rhss = streaming.mesh_reduce(local, (x,), model_args=(xms,),
                                     row_model_args=(ys_t,),
                                     accumulator=accumulator, finalize=True)
    k_mms = jax.vmap(lambda xm: kernel_matrix(kernel, xm))(
        xms).astype(acc_dtype)
    if weights is not None:
        gs, rhss, k_mms = jax.vmap(weighted_normal_eq)(
            gs, rhss, k_mms, weights)
    # n*lam in HOST float64, rounded once to the Gram dtype: the rounding
    # the single-model solve's python n*lam gets where it meets G
    nlams = jnp.asarray(n * np.asarray(jax.device_get(lams), np.float64),
                        gs.dtype)
    eps = float(jnp.finfo(gs.dtype).eps) * _eff_eps_scale(
        accumulator, steps, precision)
    body = functools.partial(_solve_models, jitter=jitter, eps=eps)
    betas = streaming.mesh_map(body, (nlams, gs, rhss, k_mms), out_rank=2,
                               rule="models")
    if weights is not None:
        betas = weights.astype(betas.dtype) * betas
    return BatchedNystromFit(beta=betas, landmarks=xms,
                             landmark_idx=landmark_sets, lams=lams)


def predict_streaming_batched(kernel: Kernel, fit_: BatchedNystromFit,
                              x_new: Array, *, tile: int | None = None,
                              backend: str | None = None,
                              precision: str | None = None) -> Array:
    """Predict all B models on shared query rows in one pass: (B, n_new).

    Unlike a lam grid (many betas, ONE landmark set) every model here
    owns its landmarks, so each tile evaluates B_loc kernel slabs — rows
    still stream once.  Mesh layout matches the batched fit:
    rows shard over "rows", models over "models"
    (`streaming.mesh_map(model_args=...)` — output dim 0 rides the model
    axis, dim 1 the rows); no collective, predict is row-parallel per model.
    """
    _require_sentinel_safe(kernel)
    xms, betas = fit_.landmarks, fit_.beta                # (B, m, d), (B, m)
    big, m, d = xms.shape
    tile = _resolve_predict_tile(tile, x_new, xms[0], backend,
                                 num_models=_models_per_chip(big))

    def local(x_loc, xms_loc, betas_loc):
        def one(xt):                                      # (t, d) -> (t, B_loc)
            def per_model(xm_b, beta_b):
                k = kernel_matrix(kernel, xt, xm_b)
                return _apply_beta(k, beta_b, precision)  # (t,)

            return jax.vmap(per_model, in_axes=(0, 0),
                            out_axes=1)(xms_loc, betas_loc)

        return streaming.tile_map(one, x_loc, tile=tile).T

    return streaming.mesh_map(local, x_new, model_args=(xms, betas),
                              out_rank=2)
