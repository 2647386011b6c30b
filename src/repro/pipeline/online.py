"""Online layer over the first-class accumulator state: incremental
`partial_fit`, decayed / sliding-window absorption, and SQUEAK-style
online landmark maintenance.

The streaming stack already expresses every expensive reduction as a
monoid fold (`repro.core.accstate`); this module drives those folds over
time instead of over one batch:

  * `OnlineState` — the pipeline's live accumulators: the banked
    normal-equation state (`nystrom.NormalEqState`, free at fit time —
    SolveStage defers the finalize of the SAME stream it already ran), an
    optional CIC deposit state (`kde.DepositState`) for density drift
    tracking, and an optional ring buffer of per-chunk states for
    sliding-window absorption.  `absorb` folds a new (x, y) chunk in at
    O(chunk · m); `solve_fit` re-runs only the O(m^3) solve.
  * decay — `absorb(decay=gamma)` exponentially forgets the past IN THE
    ACCUMULATOR DOMAIN (for the compensated strategy both hi and lo scale,
    so the banked rounding error keeps compensating the sum it belongs
    to); the effective row count decays identically, so the solve's
    n·lam regularizer tracks the true effective sample size.
  * window — monoids have no inverse, so a sliding window keeps the last
    `window` per-chunk states in a ring (`accstate.SlidingWindow`) and
    refolds O(window) merges per update instead of subtracting.
  * `OnlineLandmarks` / `OnlineLandmarkStage` — SQUEAK-style sequential
    ridge-leverage dictionary maintenance: each member keeps the uniform
    that admitted it, inclusion probabilities only ever shrink as the
    stream grows (monotone coupling), so drops are exactly the members
    whose retained uniform climbs above their re-estimated probability.
    The O(|D|^2) weighted coreset refit runs only when the dictionary
    actually changed.

Checkpointing: `OnlineState.checkpoint_state()` returns a pure pytree
(the accumulator states) that `repro.checkpoint.Manager` persists
tear-safely; `restore_checkpoint_state` folds a restored tree back in so
an interrupted stream continues bit-where-it-left-off.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import accstate, kde, nystrom, rls
from repro.core import kernels as core_kernels
from repro.pipeline import stages as stages_mod

Array = jax.Array


def _empty_like(state: nystrom.NormalEqState) -> nystrom.NormalEqState:
    """A zero-row clone of `state` (same landmarks / exec knobs) — the
    identity element fresh window chunks are absorbed into."""
    acc = accstate.wrap(state.acc.spec,
                        jax.tree.map(jnp.zeros_like, state.acc.value),
                        rows=0.0, steps=0)
    return dataclasses.replace(state, acc=acc)


@dataclasses.dataclass
class OnlineState:
    """Live accumulator state a fitted pipeline keeps absorbing into.

    ``solve`` is the source of truth; ``deposit`` (optional) tracks the
    density grid for drift diagnostics; ``window`` (optional) holds the
    per-chunk ring for sliding-window mode.  ``weights`` are the landmark
    column weights the original solve used (None for the unweighted
    default) — `solve_fit` re-applies them so an online re-solve is the
    same solve the SolveStage ran.
    """

    solve: nystrom.NormalEqState
    weights: Optional[Array] = None
    deposit: Optional[kde.DepositState] = None
    window: Optional[accstate.SlidingWindow] = None

    # ------------------------------------------------------------- absorb --
    def absorb(self, kernel: core_kernels.Kernel, x: Array, y: Array, *,
               decay: float | None = None,
               window: int | None = None) -> "OnlineState":
        """Fold a new chunk in: O(chunk · m) for the Gram stream plus an
        O(chunk · 2^d) deposit when one is attached.

        ``decay=gamma`` scales every accumulated moment (and the effective
        row count) by gamma BEFORE absorbing the chunk — exponential
        forgetting with per-call granularity.  ``window=k`` switches to
        sliding-window mode on first use (the current state becomes chunk
        0 of the ring); decay and window are different forgetting policies
        and cannot be combined.
        """
        if decay is not None and (window is not None or
                                  self.window is not None):
            raise ValueError("decay and window are mutually exclusive "
                             "forgetting policies; pick one")
        if window is not None:
            if self.window is None:
                self.window = accstate.SlidingWindow(
                    window, merge_fn=nystrom.normal_eq_merge)
                self.window.push(self.solve)
            elif self.window.window != window:
                raise ValueError(
                    f"window size is fixed at first use "
                    f"({self.window.window}); got {window}")
        x = jnp.asarray(x)
        y = jnp.asarray(y)
        if self.window is not None:
            chunk = nystrom.normal_eq_absorb(kernel, _empty_like(self.solve),
                                             x, y)
            self.window.push(chunk)
            self.solve = self.window.state()
        else:
            solve = self.solve
            if decay is not None:
                solve = nystrom.normal_eq_decay(solve, decay)
            self.solve = nystrom.normal_eq_absorb(kernel, solve, x, y)
        if self.deposit is not None:
            dep = self.deposit
            if decay is not None:
                dep = kde.deposit_decay(dep, decay)
            self.deposit = kde.deposit_absorb(dep, x)
        return self

    # -------------------------------------------------------------- solve --
    def solve_fit(self, lam: float, *,
                  jitter: float = 1e-6) -> nystrom.NystromFit:
        """Re-run the O(m^3) whitened solve on the current accumulators."""
        return nystrom.solve_from_state(self.solve, lam, jitter=jitter,
                                        weights=self.weights)

    @property
    def rows(self) -> float:
        """Effective (possibly decayed / windowed) absorbed row count."""
        return accstate.rows_of(self.solve.acc)

    # --------------------------------------------------------- checkpoint --
    def checkpoint_state(self) -> dict:
        """Pure-pytree view for `repro.checkpoint.Manager.save`.

        Window mode checkpoints the ring chunks (the folded state is
        derived data); otherwise the solve state itself, plus the deposit
        when one is attached.
        """
        tree: dict[str, Any] = {}
        if self.window is not None:
            tree["window"] = tuple(self.window.chunks)
        else:
            tree["solve"] = self.solve
        if self.deposit is not None:
            tree["deposit"] = self.deposit
        if self.weights is not None:
            tree["weights"] = self.weights
        return tree

    def restore_checkpoint_state(self, tree: dict) -> "OnlineState":
        """Adopt a tree previously produced by `checkpoint_state` (after a
        `checkpoint.Manager.restore` round-trip)."""
        if "window" in tree:
            chunks = tree["window"]
            if self.window is None:
                self.window = accstate.SlidingWindow(
                    max(1, len(chunks)), merge_fn=nystrom.normal_eq_merge)
            for chunk in chunks:
                self.window.push(chunk)
            self.solve = self.window.state()
        else:
            self.solve = tree["solve"]
        if "deposit" in tree:
            self.deposit = tree["deposit"]
        if "weights" in tree:
            self.weights = tree["weights"]
        return self


def from_context(ctx: stages_mod.StageContext, *, weighted: bool = False,
                 deposit: bool = False,
                 grid_size: int | None = None) -> OnlineState:
    """Seed an `OnlineState` from a fitted StageContext.

    The solve state is the one SolveStage banked (free — no re-stream).
    ``weighted=True`` mirrors a ``SolveStage(weighted=True)`` fold: the
    recorded importance weights keep rescaling every online re-solve.
    ``deposit=True`` additionally replays ONE O(n · 2^d) CIC pass over the
    training rows so density drift can be tracked online (only meaningful
    for the binned-KDE regime, d <= 3).
    """
    if ctx.solve_state is None:
        raise RuntimeError(
            "the fitted stage list banked no normal-equation state; "
            "include a SolveStage (and run fit/evaluate) before going "
            "online")
    weights = ctx.sample_weights if weighted else None
    state = OnlineState(solve=ctx.solve_state, weights=weights)
    if deposit:
        x = ctx.x
        h = ctx.bandwidth
        h = jnp.asarray(h if h is not None else kde.scott_bandwidth(x),
                        x.dtype)
        gs = grid_size or getattr(ctx.config, "kde_grid_size", None) \
            or kde.default_grid_size(ctx.d)
        lo, hi = kde.binned_bounds(x, x, h)
        cfg = ctx.config
        dep = kde.deposit_init(
            lo, hi, gs, dtype=x.dtype,
            tile=getattr(cfg, "kde_tile", None),
            accumulator=getattr(cfg, "accumulator", "plain"),
            backend=stages_mod.resolve_backend(cfg))
        state.deposit = kde.deposit_absorb(dep, x)
    return state


def _seed_probs(ctx: stages_mod.StageContext) -> np.ndarray:
    """Inclusion probabilities of the fitted landmark set: inverse
    importance weights when the without-replacement sampler recorded them,
    else m · leverage-probs at the sampled indices, else certain (1)."""
    idx = np.asarray(jax.device_get(ctx.landmark_idx))
    if ctx.sample_weights is not None:
        probs = 1.0 / np.clip(
            np.asarray(jax.device_get(ctx.sample_weights), np.float64),
            1.0, np.inf)
    elif ctx.leverage is not None:
        probs = len(idx) * np.asarray(
            jax.device_get(ctx.leverage.probs), np.float64)[idx]
    else:
        probs = np.ones(len(idx))
    return np.clip(probs, 1e-12, 1.0)


def _seed_from_ctx(ctx: stages_mod.StageContext, *, oversample: float,
                   seed: int | None,
                   backend: str | None) -> OnlineLandmarks:
    cfg = ctx.config
    idx = np.asarray(jax.device_get(ctx.landmark_idx))
    x_d = np.asarray(jax.device_get(ctx.x))[idx]
    y_d = np.asarray(jax.device_get(ctx.y))[idx]
    return OnlineLandmarks(
        ctx.kernel, x_d, y_d, _seed_probs(ctx),
        lam=ctx.lam, n0=float(ctx.n), oversample=oversample,
        seed=cfg.seed if seed is None else seed, jitter=cfg.jitter,
        backend=backend or stages_mod.resolve_backend(cfg), idx=idx)


# ------------------------------------------------------- online landmarks --

class OnlineLandmarks:
    """SQUEAK-style sequential ridge-leverage landmark dictionary.

    Each dictionary member j carries its admission uniform ``u_j`` and its
    current inclusion probability ``p_j`` (= 1 / importance weight).  As
    the stream grows, ridge leverage — and hence p — can only shrink
    (more data explains each point better), so re-estimated probabilities
    are clamped monotone (``p <- min(p, p_new)``) and a member is dropped
    exactly when its retained uniform climbs above its probability
    (``u_j >= p_j``).  This is the standard monotone coupling: the
    dictionary is distributed as if every point had been offered the
    CURRENT probabilities, without ever revisiting the stream.

    New points are admitted with probability min(1, oversample · RLS),
    where the RLS estimate projects onto the current weighted dictionary
    (`rls.projection_leverage`, mu = n_eff · lam).  The weighted coreset
    refit (`refit`) is O(|D|^2 · d + |D|^3) and only worth running when
    `update` reports a change.
    """

    def __init__(self, kernel: core_kernels.Kernel, x_dict: Array,
                 y_dict: Array, probs, *, lam: float, n0: float,
                 oversample: float = 2.0, seed: int = 0,
                 jitter: float = 1e-6, backend: str | None = None,
                 idx: Array | None = None):
        self.kernel = kernel
        self.x = np.asarray(x_dict)
        self.y = np.asarray(y_dict)
        p = np.clip(np.asarray(probs, np.float64), 1e-12, 1.0)
        self.p = p
        self.idx = (np.asarray(idx, np.int64) if idx is not None
                    else np.full(len(p), -1, np.int64))
        self._rng = np.random.default_rng(seed)
        # monotone coupling: a seeded member was admitted at probability p,
        # so its (unobserved) admission uniform is distributed U(0, p)
        self.u = self._rng.uniform(0.0, p)
        self.lam = float(lam)
        self.n = float(n0)              # effective stream size (drives mu)
        self.oversample = float(oversample)
        self.jitter = float(jitter)
        self.backend = backend
        self.changes = 0                # dictionary-changed update count
        self.updates = 0

    def __len__(self) -> int:
        return len(self.p)

    @property
    def weights(self) -> np.ndarray:
        """Inverse-inclusion importance weights 1 / p."""
        return 1.0 / self.p

    def _rls(self, x: np.ndarray) -> np.ndarray:
        mu = self.n * self.lam
        lev = rls.projection_leverage(
            self.kernel, jnp.asarray(x), jnp.asarray(self.x),
            jnp.asarray(self.weights, jnp.float32), mu,
            jitter=self.jitter, backend=self.backend)
        return np.asarray(jax.device_get(lev), np.float64)

    def update(self, x_new: Array, y_new: Array,
               idx_new: Array | None = None) -> bool:
        """Offer a new chunk to the dictionary; returns True when the
        dictionary changed (admissions and/or drops) — the caller's cue
        that a coreset `refit` is worth its O(|D|^2)."""
        x_new = np.asarray(x_new)
        y_new = np.asarray(y_new)
        self.updates += 1
        self.n += float(len(x_new))
        # admission: RLS of the offered points against the CURRENT dict
        q = np.clip(self.oversample * self._rls(x_new), 1e-12, 1.0)
        u = self._rng.uniform(0.0, 1.0, size=len(x_new))
        take = u < q
        changed = bool(take.any())
        if changed:
            self.x = np.concatenate([self.x, x_new[take]])
            self.y = np.concatenate([self.y, y_new[take]])
            self.p = np.concatenate([self.p, q[take]])
            self.u = np.concatenate([self.u, u[take]])
            new_idx = (np.asarray(idx_new, np.int64)[take]
                       if idx_new is not None
                       else np.full(int(take.sum()), -1, np.int64))
            self.idx = np.concatenate([self.idx, new_idx])
        # re-estimation: probabilities shrink monotonically with the
        # grown stream; members whose retained uniform now exceeds their
        # probability fall out (inclusion stays exactly Bernoulli(p))
        p_new = np.clip(self.oversample * self._rls(self.x), 1e-12, 1.0)
        self.p = np.minimum(self.p, p_new)
        keep = self.u < self.p
        if not keep.all():
            changed = True
            self.x, self.y = self.x[keep], self.y[keep]
            self.p, self.u = self.p[keep], self.u[keep]
            self.idx = self.idx[keep]
        if changed:
            self.changes += 1
        return changed

    def refit(self, lam: float | None = None, *,
              jitter: float | None = None) -> nystrom.NystromFit:
        """Weighted coreset KRR on the dictionary (importance weights
        1/p correct the inclusion bias): beta solves
        K^T W K beta + n lam K beta = K^T W y on the |D| x |D| system."""
        lam = self.lam if lam is None else float(lam)
        jitter = self.jitter if jitter is None else float(jitter)
        xd = jnp.asarray(self.x, jnp.float32)
        k = core_kernels.kernel_matrix(self.kernel, xd)
        w = jnp.asarray(self.weights, k.dtype)
        g = k.T @ (w[:, None] * k)
        rhs = k.T @ (w * jnp.asarray(self.y, k.dtype))
        n_eff = float(np.sum(self.weights))
        beta = nystrom.solve_normal_eq(g, rhs, k, n_eff, lam, jitter=jitter)
        return nystrom.NystromFit(beta=beta, landmarks=xd,
                                  landmark_idx=jnp.asarray(self.idx,
                                                           jnp.int32),
                                  lam=lam)


def seed_landmarks(pipeline, *, oversample: float = 2.0,
                   seed: int | None = None,
                   backend: str | None = None) -> OnlineLandmarks:
    """Seed an `OnlineLandmarks` dictionary from a fitted pipeline.

    Inclusion probabilities come from the fitted artifacts: the inverse
    Gumbel-top-k importance weights when the without-replacement sampler
    recorded them, else m · leverage-probs at the sampled indices, else
    uniform 1 (fixed landmarks — every member certain until re-estimated).
    """
    ctx = pipeline._ctx
    if ctx is None or ctx.fit is None:
        raise RuntimeError("seed_landmarks needs a fitted pipeline")
    return _seed_from_ctx(ctx, oversample=oversample, seed=seed,
                          backend=backend)


class OnlineLandmarkStage(stages_mod.Stage):
    """Stage-shaped carrier for online landmark maintenance.

    Appended to a pipeline's stage list, it seeds an `OnlineLandmarks`
    dictionary from the fold's fitted artifacts at fit time and exposes it
    as ``self.landmarks`` — so a serving loop can keep calling
    ``stage.landmarks.update(x_new, y_new)`` / ``.refit()`` after the fit
    without re-deriving the seed state.  It provides no context artifact
    (the dictionary lives on the stage, across folds).
    """

    name = "online_landmarks"
    requires = ("fit",)
    provides = ()

    def __init__(self, *, oversample: float = 2.0, seed: int | None = None,
                 backend: str | None = None):
        self.oversample = oversample
        self.seed = seed
        self.backend = backend
        self.landmarks: OnlineLandmarks | None = None

    def run(self, ctx: stages_mod.StageContext) -> None:
        self.landmarks = _seed_from_ctx(ctx, oversample=self.oversample,
                                        seed=self.seed,
                                        backend=self.backend)
