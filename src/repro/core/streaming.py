"""Unified streaming tile-reduction engine with pluggable accumulation.

Every streaming loop in this codebase reduces (or maps) row slabs of an
(n, ...) array through a per-tile computation: the Nystrom normal equations
(`nystrom.normal_eq_state`'s pass: `nystrom.scan_normal_eq` and its
mesh-sharded wrapper), the CIC deposit of the binned KDE (`kde.scatter_cic`
and its per-chip twin in `core.distributed.kde_binned_sharded_multi`), and
the batched predicts (`nystrom.predict_streaming[_batched]`).  Historically
each re-implemented tiling, ragged-tail padding, sharding and accumulation
by hand — every numerics fix (e.g. EXACT_DIST_D) had to land in four
places.  This module owns that plumbing once:

  * `tile_reduce`  — row-slab tiling, ragged-tail padding (sentinel rows for
    kernel maps, zero rows + zero weights for deposits), a `lax.scan` over
    the slabs, and a pluggable accumulator strategy;
  * `multi_reduce` — one tile scan driving N pluggable accumulators at once
    (fused Gram+rhs+score-moment passes; `MultiAccumulator` composes per-slot
    strategies tuple-wise and is bit-equal to sequential passes per slot);
  * `tile_map`     — the same tiling for per-row outputs (predict);
  * `mesh_reduce` / `mesh_map` — optional shard_map execution over the
    "rows" logical axis (`repro.distributed.sharding`), with the accumulator
    STATE — not the finalized value — crossing the psum.

Accumulator strategies (`get(name)`):

  * ``plain``       — the historical fp32 running sum.  Bit-equal to the
    pre-engine hand-rolled loops (locked by tests/test_streaming_engine.py).
  * ``compensated`` — Kahan/Neumaier two-float error-carrying sum: the carry
    is a (hi, lo) pair per output leaf; each tile update is folded in with
    an error-free two-sum and the rounding error is banked in lo.  The pair
    survives the cross-chip psum (hi and lo reduce separately) and is only
    collapsed by `finalize`.  Cross-tile accumulation error drops from
    O(steps) * eps to the within-tile floor, which lets the solve
    (`nystrom.solve_from_state`) lower its spectral noise-floor cutoff by
    `EPS_SCALE["compensated"]` and keep whitened directions that plain fp32
    must truncate (ROADMAP: the fp32 scale ceiling).

The same strategy runs inside the Pallas `gram` kernel body as a two-float
VMEM accumulator (`repro.kernels.gram`), so the TPU path shares the lower
noise floor; `repro.kernels.dispatch` threads ``accumulator=`` through both
backends.
"""

from __future__ import annotations

import functools
import types
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core.kernels import pad_rows_sentinel, round_up

Array = jax.Array

ACCUMULATORS = ("plain", "compensated")

# Residual accumulation-noise scale, relative to eps(dtype), that
# the solve (`nystrom.solve_from_state`) may assume for a Gram built by each
# strategy.
# Plain fp32 accumulation noise sits at ~eps * lambda_max(G); the
# compensated sum removes the cross-tile term, leaving the within-tile dot
# rounding — measured ≳30x below the plain floor on the n ≥ 1e5 streams the
# regression tests lock (tests/test_streaming_engine.py), so 1/32 is the
# conservative factor by which the spectral truncation floor recedes.
EPS_SCALE = {"plain": 1.0, "compensated": 1.0 / 32.0}


def two_sum(a: Array, b: Array) -> tuple[Array, Array]:
    """Error-free transformation: s fl= a + b, e = (a + b) - s exactly.

    Knuth's branch-free TwoSum (6 flops); valid for any rounding direction
    and magnitudes.  XLA does not reassociate float arithmetic, so the
    cancellation pattern survives compilation on every backend.
    """
    s = a + b
    bb = s - a
    err = (a - (s - bb)) + (b - bb)
    return s, err


def _tree_add(acc, update):
    return jax.tree.map(jnp.add, acc, update)


class PlainAccumulator:
    """The historical running sum; `state` IS the value."""

    name = "plain"

    def init(self, zeros):
        return zeros

    def add(self, state, update, combine):
        return combine(state, update)

    def merge(self, a, b):
        # IEEE addition is commutative (not associative), so merge order
        # of a PAIR is bit-stable; chains of merges are order-sensitive
        # like any float sum.
        return _tree_add(a, b)

    def psum(self, state, axes):
        return jax.lax.psum(state, axes)

    def finalize(self, state):
        return state


class CompensatedAccumulator:
    """Kahan/Neumaier two-float sum; `state` is a (hi, lo) pair of trees.

    Non-additive `combine`s (the CIC scatter) are folded in by materializing
    the tile's dense delta against a zero value first — `combine` must
    therefore satisfy combine(0, u) == the additive delta of u, which every
    scatter/segment-sum update does.
    """

    name = "compensated"

    def init(self, zeros):
        return (zeros, jax.tree.map(jnp.zeros_like, zeros))

    def add(self, state, update, combine):
        hi, lo = state
        if combine is _tree_add:
            delta = update
        else:
            delta = combine(jax.tree.map(jnp.zeros_like, hi), update)
        s = jax.tree.map(jnp.add, hi, delta)
        err = jax.tree.map(
            lambda h, d, ss: (h - (ss - (ss - h))) + (d - (ss - h)), hi,
            delta, s)
        return (s, jax.tree.map(jnp.add, lo, err))

    def merge(self, a, b):
        # Error-free pair merge: the hi parts combine through TwoSum and
        # the rounding error lands in lo alongside both carried errors —
        # merging two compensated states loses nothing beyond what a
        # continued single stream would have lost.
        hi_a, lo_a = a
        hi_b, lo_b = b
        s = jax.tree.map(lambda x, y: two_sum(x, y)[0], hi_a, hi_b)
        err = jax.tree.map(lambda x, y: two_sum(x, y)[1], hi_a, hi_b)
        lo = jax.tree.map(lambda la, lb, e: la + lb + e, lo_a, lo_b, err)
        return (s, lo)

    def psum(self, state, axes):
        # hi and lo reduce SEPARATELY: the pair crosses the collective
        # un-collapsed, so per-chip compensation is not thrown away at the
        # all-reduce (finalize folds the psummed lo back in).
        return jax.lax.psum(state, axes)

    def finalize(self, state):
        hi, lo = state
        return jax.tree.map(jnp.add, hi, lo)


class MultiAccumulator:
    """Tuple-of-slots composition: slot i runs its own strategy + combine.

    State is a tuple of per-slot accumulator states, so one tile scan can
    drive N independent reductions (Gram + rhs + predict moments + ...) off
    a single pass over x.  Each slot's arithmetic is the *same op sequence*
    it would run in its own `tile_reduce` — XLA never reassociates floats —
    so a fused plain-slot reduction is bit-equal to the sequential one
    (locked by tests/test_multi_reduce.py).  Compensated slots keep their
    (hi, lo) pair per slot; `psum`/`finalize` delegate slot-wise, which is
    what lets the pair survive a fused cross-chip reduction un-collapsed.
    """

    def __init__(self, accumulators: Sequence[str | Any],
                 combines: Sequence[Callable | None] | None = None):
        self.accumulators = tuple(get(a) for a in accumulators)
        if combines is None:
            combines = (None,) * len(self.accumulators)
        if len(combines) != len(self.accumulators):
            raise ValueError("combines and accumulators length mismatch")
        self.combines = tuple(c if c is not None else _tree_add
                              for c in combines)
        self.name = "multi(" + ",".join(
            a.name for a in self.accumulators) + ")"

    def init(self, zeros):
        if len(zeros) != len(self.accumulators):
            raise ValueError(
                f"init expects {len(self.accumulators)} slot zeros, "
                f"got {len(zeros)}")
        return tuple(a.init(z) for a, z in zip(self.accumulators, zeros))

    def add(self, state, update, combine):
        del combine  # per-slot combines are fixed at construction
        return tuple(
            a.add(s, u, c) for a, s, u, c in
            zip(self.accumulators, state, update, self.combines))

    def merge(self, a, b):
        return tuple(
            acc.merge(sa, sb) for acc, sa, sb in
            zip(self.accumulators, a, b))

    def psum(self, state, axes):
        return tuple(
            a.psum(s, axes) for a, s in zip(self.accumulators, state))

    def finalize(self, state):
        return tuple(
            a.finalize(s) for a, s in zip(self.accumulators, state))


_STRATEGIES = {"plain": PlainAccumulator(), "compensated": CompensatedAccumulator()}


def get(accumulator: str | Any) -> Any:
    """Resolve an accumulator name ('plain' | 'compensated') or instance."""
    if isinstance(accumulator, str):
        try:
            return _STRATEGIES[accumulator]
        except KeyError:
            raise ValueError(f"unknown accumulator {accumulator!r}; "
                             f"pick from {ACCUMULATORS}") from None
    return accumulator


def eps_scale(accumulator: str | Any, steps: int | None = None) -> float:
    """Noise-floor scale for the solve (`nystrom.solve_from_state`, through
    `nystrom.solve_normal_eq`'s ``eps_scale``; see EPS_SCALE).

    Compensation removes only the CROSS-TILE accumulation error, so the
    floor may recede by at most the number of scan steps the stream ran:
    with `steps` given, the compensated scale is max(1/32, 1/steps) — a
    single-tile stream (steps == 1) has nothing to compensate and keeps the
    plain floor (lowering it there would retain directions whose fp32
    content is within-dot / kernel-eval noise the two-float sum never
    touched — empirically WORSE than plain at small n).
    """
    name = accumulator if isinstance(accumulator, str) else accumulator.name
    scale = EPS_SCALE.get(name, 1.0)
    if steps is not None and scale < 1.0:
        scale = max(scale, 1.0 / max(int(steps), 1))
    return scale


# ------------------------------------------------------------------ tiling --

def _pad_rows(x: Array, rows: int, pad: str) -> Array:
    if pad == "sentinel":
        return pad_rows_sentinel(x, rows)
    if pad == "zero":
        return jnp.pad(x, ((0, rows - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))
    raise ValueError(f"unknown pad mode {pad!r}; pick 'sentinel' or 'zero'")


def _tiles(x: Array, t: int, np_: int, pad: str) -> Array:
    return _pad_rows(x, np_, pad).reshape((np_ // t, t) + x.shape[1:])


def tile_reduce(
    emit: Callable[..., Any],
    x: Array,
    aux: Sequence[Array] = (),
    *,
    tile: int | None,
    init: Any,
    combine: Callable[[Any, Any], Any] | None = None,
    accumulator: str | Any = "plain",
    pad: str = "sentinel",
    finalize: bool = True,
    init_state: Any = None,
    return_state: bool = False,
) -> Any:
    """Reduce `tile`-row slabs of x (+ row-aligned aux arrays) into `init`.

    ``emit(x_tile, *aux_tiles)`` produces the tile's update;
    ``combine(value, update)`` folds it into the running value (default:
    leafwise add — the Gram case; pass a scatter for deposits).  Ragged
    tails are padded per ``pad`` ("sentinel" parks extra rows at the
    ROW_SENTINEL coordinate so kernel maps evaluate to exactly 0; "zero"
    zero-pads — deposits must then carry a zero-padded weight aux so padded
    rows deposit nothing).  aux arrays are always zero-padded.

    ``accumulator`` picks the strategy (module docstring).  With
    ``finalize=False`` (or ``return_state=True``) the raw accumulator
    state is returned — the form `mesh_reduce` psums across chips and
    `repro.core.accstate` wraps as a first-class value.  With
    ``init_state=`` the scan carry STARTS from a previously returned raw
    state instead of `acc.init(init)`: absorbing a stream in tile-aligned
    chunks is then the same op sequence as one uninterrupted fold, so a
    chained absorb is bit-equal to the one-shot reduction (the incremental
    `partial_fit` contract).  A whole-array slab still runs as a one-step
    `lax.scan`: the scan body is compiled as one fused computation exactly
    like the historical hand-rolled loops, which is what makes plain mode
    bit-equal to them (an eager shortcut would round FMA-fused
    subexpressions differently on CPU).
    """
    acc = get(accumulator)
    combine = combine if combine is not None else _tree_add
    n = x.shape[0]
    t = min(tile, n) if tile else n
    state = acc.init(init) if init_state is None else init_state
    np_ = round_up(n, t)
    slabs = (_tiles(x, t, np_, pad),) + tuple(
        _tiles(a, t, np_, "zero") for a in aux)

    def step(carry, slab):
        return acc.add(carry, emit(*slab), combine), None

    state, _ = jax.lax.scan(step, _vary_like_step(step, state, slabs), slabs)
    if return_state:
        return state
    return acc.finalize(state) if finalize else state


def _vary_like_step(step, state, slabs):
    """Cast the scan's initial carry to the mesh axes its updates vary over.

    Inside a `shard_map` body (`mesh_reduce`) the row slabs vary over the
    data axes (and model slabs over the model axes), while a zero init does
    not; `lax.scan` needs the carry's type to be a fixed point of `step`.
    Outside any manual mesh context this is the identity.
    """
    if not jax.sharding.get_abstract_mesh().manual_axes:
        return state
    out = jax.eval_shape(lambda c, s: step(c, s)[0], state,
                         jax.tree.map(lambda a: a[0], slabs))

    def cast(leaf, ref):
        missing = tuple(sorted(ref.vma - jax.typeof(leaf).vma))
        return jax.lax.pcast(leaf, missing, to="varying") if missing else leaf

    return jax.tree.map(cast, state, out)


def multi_reduce(
    emit: Callable[..., Any],
    x: Array,
    aux: Sequence[Array] = (),
    *,
    tile: int | None,
    inits: Sequence[Any],
    accumulators: Sequence[str | Any] | None = None,
    combines: Sequence[Callable | None] | None = None,
    pad: str = "sentinel",
    finalize: bool = True,
    init_state: Any = None,
    return_state: bool = False,
) -> Any:
    """One tile scan driving N pluggable accumulators at once.

    ``emit(x_tile, *aux_tiles)`` returns a TUPLE of per-slot updates —
    typically sharing expensive intermediates (the kernel tile) across
    slots.  Slot i is accumulated by ``accumulators[i]`` (default: all
    plain) folding with ``combines[i]`` (default: leafwise add) into
    ``inits[i]``.  Everything else (padding, scan, finalize semantics,
    ``init_state=``/``return_state=`` state threading) matches
    `tile_reduce`; with ``finalize=False`` the returned state is a tuple
    of per-slot states — the form `mesh_reduce` psums when given the same
    `MultiAccumulator` instance.
    """
    accs = tuple(accumulators) if accumulators is not None else (
        ("plain",) * len(tuple(inits)))
    multi = MultiAccumulator(accs, combines)
    return tile_reduce(emit, x, aux, tile=tile, init=tuple(inits),
                       accumulator=multi, pad=pad, finalize=finalize,
                       init_state=init_state, return_state=return_state)


def tile_map(
    fn: Callable[[Array], Array],
    x: Array,
    *,
    tile: int,
    pad: str = "sentinel",
) -> Array:
    """Map `fn` over `tile`-row slabs of x; returns the stacked (n, ...) out.

    The (tile, ...) slab output dies with each `lax.map` step, so peak
    transient memory is O(tile * out_cols) regardless of n — the predict
    contract (`nystrom.predict_streaming`).
    """
    n = x.shape[0]
    t = min(tile, n)
    np_ = round_up(n, t)
    out = jax.lax.map(fn, _tiles(x, t, np_, pad))
    return out.reshape((np_,) + out.shape[2:])[:n]


# -------------------------------------------------------------------- mesh --

def _active_axes(rule: str, shape):
    """(mesh, axes) when the active mesh's `rule` divides dim 0 of `shape`.

    The generic resolver behind both logical stream axes this engine knows:
    "rows" (the data/sample dim — reductions PSUM over it) and "models" (the
    independent-work dim — h/lam candidates, per-tenant models — which
    SHARDS, never reduces).  Under a 1D ("data",) mesh the "models" rule
    resolves to None and every model-axis path degenerates to the
    replicated 1D behavior.
    """
    from repro.distributed import sharding as shd
    act = shd.active()
    if act is None:
        return None, None
    axes = act.spec((rule,) + (None,) * (len(shape) - 1), shape)[0]
    return (act.mesh, axes) if axes is not None else (None, None)


def _active_rows(shape):
    """(mesh, rows_axes) when the active mesh's "rows" rule divides dim 0."""
    return _active_axes("rows", shape)


def _row_spec(axes, ndim: int):
    from jax.sharding import PartitionSpec as P
    return P(axes, *([None] * (ndim - 1)))


def _axes_tuple(axes) -> tuple:
    return (axes,) if isinstance(axes, str) else tuple(axes)


def _mesh_axes_count(mesh, axes) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    count = 1
    for a in _axes_tuple(axes):
        count *= sizes[a]
    return count


def row_shard_count(shape) -> int:
    """How many chips the "rows" rule splits dim 0 of `shape` across (1
    with no active mesh / non-dividing axis).  Callers sizing per-chip work
    — e.g. the scan-step count behind `eps_scale` — must divide by this:
    each chip streams only n/C rows, so a stream that is one tile PER CHIP
    has no cross-tile error to compensate even when the global n spans
    several tiles.  Counts DATA-axis shards only: under a 2D (data, model)
    mesh the model axis replicates (or shards independent work), it never
    splits the row stream, so it must not inflate the step budget."""
    mesh, axes = _active_rows(shape)
    if mesh is None:
        return 1
    return _mesh_axes_count(mesh, axes)


def model_shard_count(num_models: int) -> int:
    """How many chips the "models" rule splits an independent-work axis of
    length `num_models` across (1 with no active mesh, a 1D data mesh, or a
    non-dividing model axis)."""
    mesh, axes = _active_axes("models", (num_models,))
    if mesh is None:
        return 1
    return _mesh_axes_count(mesh, axes)


def _reuse_key(fn: Callable) -> Any:
    """A hashable value that stands for `fn` across calls, or None.

    A function that closes over nothing stands for itself, and a
    `functools.partial` of one for the function with its bound arguments
    and their types.  Anything else (a closure made anew on each call, or
    a bound argument that does not hash) has no such value.
    """
    func, args, kwargs = fn, (), ()
    if isinstance(fn, functools.partial):
        func, args = fn.func, fn.args
        kwargs = tuple(sorted(fn.keywords.items()))
    if not isinstance(func, types.FunctionType) or func.__closure__:
        return None
    values = args + tuple(v for _, v in kwargs)
    key = (func, args, kwargs, tuple(type(v) for v in values))
    try:
        hash(key)
    except TypeError:
        return None
    return key


def _build_program(local, mesh, in_specs, out_specs, psum_axes,
                   accumulator):
    """``jit(shard_map(local))``, psumming its accumulator state over
    ``psum_axes`` when there are any."""
    if psum_axes:
        acc = get(accumulator)

        def body(*args):
            return acc.psum(local(*args), psum_axes)
    else:
        body = local
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs))


@functools.lru_cache(maxsize=64)
def _reused_program(key, mesh, in_specs, out_specs, psum_axes, accumulator):
    func, args, kwargs, _ = key
    return _build_program(functools.partial(func, *args, **dict(kwargs)),
                          mesh, in_specs, out_specs, psum_axes, accumulator)


def _program(local, mesh, in_specs, out_specs, psum_axes=(),
             accumulator="plain"):
    """The compiled shard_map program of `local` on `mesh`: built once and
    kept (with jit's own cache per shape) when `local` has a `_reuse_key`,
    otherwise built afresh, so that it traces and compiles on every call."""
    key = _reuse_key(local)
    if key is None:
        return _build_program(local, mesh, in_specs, out_specs, psum_axes,
                              accumulator)
    return _reused_program(key, mesh, in_specs, out_specs, psum_axes,
                           accumulator)


def mesh_reduce(
    local: Callable[..., Any],
    row_args: Sequence[Array],
    rep_args: Sequence[Array] = (),
    *,
    model_args: Sequence[Array] = (),
    row_model_args: Sequence[Array] = (),
    accumulator: str | Any = "plain",
    finalize: bool = True,
    init_state: Any = None,
    return_state: bool = False,
) -> Any:
    """Row-sharded reduction: psum `local`'s accumulator state across chips.

    ``local(*row_slabs, *row_model_slabs, *model_slabs, *rep_args)`` must
    return accumulator STATE (i.e. it ran its own `tile_reduce`/backend
    kernel with ``finalize=False``).  Under an active mesh whose "rows"
    rule divides the leading dim, each device reduces its local row slab
    and the state is psum-reduced — for "compensated" the (hi, lo) pair
    crosses the collective un-collapsed.  Otherwise `local` runs once on
    the full arrays (transparent no-op).

    Under a mesh, the reduction and its psum run as one jitted
    ``shard_map`` program.  It compiles once per mesh, specs, accumulator
    and argument shapes, and later calls reuse it, when `local` is a
    function that closes over nothing or a `functools.partial` of one with
    hashable arguments; a closure made anew on each call compiles a new
    program on every call.

    2D (data x model) meshes: the psum covers the DATA axes only — the
    model axis shards independent work instead of reducing.  ``model_args``
    are sharded over the "models" rule on their leading dim (per-model
    landmark sets, per-model scalars); ``row_model_args`` are (rows,
    models)-shaped and shard BOTH ways (per-tenant responses riding the
    shared row stream).  When model args are present every leaf of the
    returned state must carry the model axis as its LEADING dim — the
    output stays model-sharded (out spec P(model_axes)) and assembles to
    the full (models, ...) stack, already psummed over data.  With no
    "models"-mapped mesh axis (a 1D data mesh) the model args are simply
    replicated and `local` computes every model — the transparent-fallback
    contract the bit-parity tests lock.

    ``init_state=`` is a prior raw state merged in AFTER the collective
    (threading it through the psum would multiply the replicated prior by
    the chip count); ``return_state=True`` returns the raw merged state.
    """
    from jax.sharding import PartitionSpec as P

    acc = get(accumulator)
    mesh, axes = _active_rows(row_args[0].shape)
    model_axes = None
    if model_args or row_model_args:
        probe = model_args[0].shape if model_args else (
            row_model_args[0].shape[1],)
        m_mesh, model_axes = _active_axes("models", tuple(probe[:1]))
        if mesh is None and m_mesh is not None:
            mesh = m_mesh      # model-only sharding (rows fell back local)
    if mesh is None:
        state = local(*row_args, *row_model_args, *model_args, *rep_args)
    else:
        ax_tuple = _axes_tuple(axes) if axes is not None else ()
        in_specs = (
            tuple(_row_spec(axes, a.ndim) for a in row_args)
            + tuple(P(axes, model_axes) for a in row_model_args)
            + tuple(_row_spec(model_axes, a.ndim) for a in model_args)
            + tuple(P(*([None] * a.ndim)) for a in rep_args))
        out_specs = P(model_axes) if model_axes is not None else P()
        state = _program(local, mesh, in_specs, out_specs, ax_tuple,
                         accumulator)(
            *row_args, *row_model_args, *model_args, *rep_args)
    if init_state is not None:
        state = acc.merge(init_state, state)
    if return_state:
        return state
    return acc.finalize(state) if finalize else state


def mesh_map(
    local: Callable[..., Array],
    x: Array | Sequence[Array],
    rep_args: Sequence[Array] = (),
    *,
    model_args: Sequence[Array] = (),
    out_rank: int = 1,
    rule: str = "rows",
) -> Array:
    """Sharded map: `local(x_loc, *rep_args)` -> (n_loc, ...) per chip.

    Embarrassingly parallel (no collective); `out_rank` is the rank of
    local's output, whose leading dim stays split.  ``rule`` names the
    logical axis that splits dim 0 of x: "rows" (the default) or "models"
    (independent work, such as a model batch).  ``x`` may be a tuple of
    arrays sharing that dim, passed to `local` in order.  With no active
    mesh (or a non-dividing axis) this is `local(x, *rep_args)`.  Under a
    mesh it runs as one jitted ``shard_map`` program, compiled once and
    reused on the terms `mesh_reduce` states.

    With ``model_args`` (leading-dim model-sharded, like `mesh_reduce`) the
    call signature becomes ``local(x_loc, *model_slabs, *rep_args)`` and
    the output is (models_loc, rows_loc, ...): dim 0 rides the model axis,
    dim 1 the rows — the batched-predict layout.  On a 1D data mesh the
    model args replicate and dim 0 is the full model axis.
    """
    from jax.sharding import PartitionSpec as P

    xs = tuple(x) if isinstance(x, (tuple, list)) else (x,)
    mesh, axes = _active_axes(rule, xs[0].shape)
    model_axes = None
    if model_args:
        m_mesh, model_axes = _active_axes("models", model_args[0].shape)
        if mesh is None and m_mesh is not None:
            mesh = m_mesh
    if mesh is None:
        return local(*xs, *model_args, *rep_args)
    in_specs = (tuple(_row_spec(axes, a.ndim) for a in xs)
                + tuple(_row_spec(model_axes, a.ndim) for a in model_args)
                + tuple(P(*([None] * a.ndim)) for a in rep_args))
    if model_args:
        out_specs = P(model_axes, axes, *([None] * (out_rank - 2)))
    else:
        out_specs = _row_spec(axes, out_rank)
    return _program(local, mesh, in_specs, out_specs)(*xs, *model_args,
                                                      *rep_args)


def state_nbytes(accumulator: str | Any, zeros: Any) -> int:
    """Bytes of an accumulator's state over values shaped as ``zeros`` (a
    tree of `jax.ShapeDtypeStruct`): what one chip all-reduces when
    `mesh_reduce` psums that state."""
    state = jax.eval_shape(get(accumulator).init, zeros)
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(state))
