"""Roofline-guided tile autotuning for the streaming hot paths.

After PR 5 every streaming reduction rides `repro.core.streaming`, but its
tile sizes were hardcoded (`tile=8192` pipeline default, `bm=bn=256` in the
Pallas gram kernel) while BENCH_pipeline.json shows tile choice alone swings
the solve ~2x.  This module makes ``tile=None`` mean "autotune":

  1. **Ladder** — `candidate_tiles` builds a small pow2 candidate set per
     (op, n, m, d, dtype) bounded by the slab-memory budget;
  2. **Model**  — `model_seconds` ranks it with a per-step roofline
     (max(flops/peak, bytes/bw) + fixed step overhead, with a cache-spill
     penalty once the slab outgrows the fast memory level;
     `repro.roofline.analysis.DEVICE_SPECS` supplies the constants);
  3. **Measure** — when measurement is enabled (`set_measure(True)`, the
     ``measured()`` context, or ``REPRO_AUTOTUNE=1``), the top
     `MEASURE_TOP_K` candidates run a one-off micro-benchmark on synthetic
     data (a couple of tiles' worth of rows, extrapolated to the full
     stream) and the argmin wins.  Off by default so imports, tests and
     library callers never pay a tuning pause;
  4. **Cache**  — choices persist in-memory AND on disk
     (``REPRO_TUNE_CACHE`` or ``~/.cache/repro/autotune.json``), keyed by
     device kind + shape bucket (pow2-bucketed n and m), so warm runs pay
     zero tuning cost and a measured choice is never re-measured.

The three tuned ops mirror `repro.kernels.dispatch`:

  * ``gram``    — the Nystrom normal-equation row stream
    (`nystrom.scan_normal_eq` tile on XLA; Pallas `gram` bm/bn on TPU);
  * ``deposit`` — the binned-KDE CIC scatter (`kde.scatter_cic` tile on
    XLA; Pallas `kde_binned` bm on TPU; ``m`` is the per-axis grid size);
  * ``predict`` — the batched predict row stream
    (`nystrom.predict_streaming` tile).

Everything here is shape-level plumbing: resolving a plan NEVER perturbs
numerics.  ``op(tile=None)`` is bit-equal to ``op(tile=plan.tile)`` — the
plan only picks the integer (locked by tests/test_autotune.py).
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import tempfile
import time
import warnings
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.precision import PRECISIONS
from repro.roofline.analysis import DeviceSpec, device_spec

Array = jax.Array

OPS = ("gram", "deposit", "predict")

# The precision modes ``precision=None`` may resolve to JOINTLY with the
# tile.  bf16x2 is excluded: it is faster still on MXU but raises the Gram
# noise floor ~256x (precision.EPS_SCALE), so it must be an explicit
# caller choice, never an autotuner surprise.
AUTO_PRECISIONS = ("fp32", "bf16x3")

DEFAULT_TILE = 8192      # the historical hardcoded pipeline default
DEFAULT_BM = 256         # Pallas gram/deposit row block
DEFAULT_BN = 256         # Pallas gram column block

MEASURE_TOP_K = 4        # model-ranked candidates the micro-bench times
MIN_TILE = 512           # smallest ladder rung (per-step overhead floor)
MAX_TILE = 131072        # largest rung (slab memory ceiling at prod m)
_SLAB_BYTES_CAP = 512e6  # hard sanity cap on tile * m * dtype_bytes

_CACHE_VERSION = 2


@dataclasses.dataclass(frozen=True)
class Plan:
    """One resolved execution plan for a streamed op.

    ``source`` records provenance: "model" (analytic ranking only),
    "measured" (micro-benchmarked this process), "cache" (recalled from a
    prior resolution — warm runs), "default" (fallback when resolution is
    impossible, e.g. n == 0).  ``tuning_seconds`` is the wall-clock this
    resolution spent measuring (0.0 for model/cache/default).
    ``precision`` is the Gram-contraction mode the plan was resolved for —
    either echoed back from the caller's pin, or (``precision=None``
    requests on the gram op) the mode the roofline model / micro-benchmark
    chose jointly with the tile.
    """

    op: str
    tile: int
    bm: int = DEFAULT_BM
    bn: int = DEFAULT_BN
    source: str = "default"
    tuning_seconds: float = 0.0
    precision: str = "fp32"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


# ----------------------------------------------------------------- gating --

_MEASURE: bool | None = None     # tri-state: None -> env decides


def set_measure(on: bool | None) -> None:
    """Force measurement on/off; None restores the REPRO_AUTOTUNE env gate."""
    global _MEASURE
    _MEASURE = on


def measuring() -> bool:
    """Whether plan resolution may run micro-benchmarks right now."""
    if _MEASURE is not None:
        return _MEASURE
    return os.environ.get("REPRO_AUTOTUNE", "0").lower() in ("1", "true",
                                                             "measure", "on")


@contextlib.contextmanager
def measured(on: bool = True):
    """Scope in which plan resolution may (or may not) micro-benchmark."""
    global _MEASURE
    prev = _MEASURE
    _MEASURE = on
    try:
        yield
    finally:
        _MEASURE = prev


def _can_measure() -> bool:
    """Measurement compiles and runs real kernels — refuse under a trace
    (e.g. a dispatch call inside a shard_map body) and on backends where the
    candidate kernels only run in interpret mode (Pallas off-TPU)."""
    return jax.core.trace_ctx.is_top_level()


# ------------------------------------------------------------------ cache --

_MEMORY: dict[str, dict] = {}
_DISK_LOADED = False


def cache_path() -> str:
    """On-disk plan cache location (REPRO_TUNE_CACHE overrides)."""
    env = os.environ.get("REPRO_TUNE_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "autotune.json")


def _entry_valid(v) -> bool:
    """Shape-check one cache entry before trusting it (a truncated write or
    concurrent editor can leave arbitrary JSON behind)."""
    return (isinstance(v, dict)
            and isinstance(v.get("tile"), int) and v["tile"] > 0
            and v.get("source") in ("model", "measured"))


def _load_disk() -> None:
    global _DISK_LOADED
    if _DISK_LOADED:
        return
    _DISK_LOADED = True
    path = cache_path()
    try:
        with open(path) as f:
            payload = json.load(f)
    except FileNotFoundError:
        return                         # cold cache: the normal first run
    except (OSError, ValueError) as e:  # ValueError covers JSONDecodeError
        warnings.warn(
            f"ignoring unreadable autotune plan cache at {path} ({e}); "
            "plans will be re-tuned from scratch", RuntimeWarning,
            stacklevel=2)
        return
    if not isinstance(payload, dict) or "version" not in payload:
        warnings.warn(
            f"autotune plan cache at {path} has no version key (corrupted "
            "or concurrently rewritten); re-tuning from scratch",
            RuntimeWarning, stacklevel=2)
        return
    if payload["version"] != _CACHE_VERSION:
        return   # clean older/newer format: silently start cold
    entries = payload.get("entries")
    if not isinstance(entries, dict):
        warnings.warn(
            f"autotune plan cache at {path} has malformed entries; "
            "re-tuning from scratch", RuntimeWarning, stacklevel=2)
        return
    bad = 0
    for k, v in entries.items():
        if _entry_valid(v):
            _MEMORY.setdefault(k, v)
        else:
            bad += 1
    if bad:
        warnings.warn(
            f"dropped {bad} malformed entr{'y' if bad == 1 else 'ies'} from "
            f"the autotune plan cache at {path}; they will be re-tuned",
            RuntimeWarning, stacklevel=2)


def _save_disk() -> None:
    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                                   prefix=".autotune-")
        with os.fdopen(fd, "w") as f:
            json.dump({"version": _CACHE_VERSION, "entries": _MEMORY}, f,
                      indent=1, sort_keys=True)
        os.replace(tmp, path)   # atomic: concurrent runs never see half a file
    except OSError:
        pass   # read-only FS etc.: in-memory cache still works


def clear_cache() -> None:
    """Drop the in-memory cache and delete the on-disk file."""
    global _DISK_LOADED
    _MEMORY.clear()
    _EXECUTABLES.clear()
    _DISK_LOADED = True   # don't resurrect the file we are about to delete
    try:
        os.remove(cache_path())
    except OSError:
        pass
    _DISK_LOADED = False


# ----------------------------------------------------- compiled executables --

_EXECUTABLES: dict[tuple, Callable] = {}


def cached_executable(key: tuple, build: Callable[[], Callable]) -> Callable:
    """Process-lifetime jit cache for plan-resolved streamed ops.

    An autotuned plan is worthless if every call re-traces the loop it
    tuned, so callers that resolved their tile through `plan_for` wrap the
    hot computation here: first call per `key` (op + kernel params + tile +
    concrete shapes/dtype) jits `build()`'s closure, later calls reuse the
    compiled artifact — the FFTW-wisdom move, applied to XLA executables.
    Explicit-tile calls never come through here: their op-by-op eager
    semantics are the historical bit-parity contract.
    """
    fn = _EXECUTABLES.get(key)
    if fn is None:
        fn = _EXECUTABLES[key] = jax.jit(build())
    return fn


def _bucket(v: int) -> int:
    """Pow2-ceil shape bucketing: nearby shapes share one plan."""
    return 1 << max(0, int(v) - 1).bit_length() if v > 1 else 1


def shape_key(op: str, n: int, m: int, d: int, *, dtype=jnp.float32,
              backend: str = "xla", accumulator: str = "plain",
              precision: str = "fp32",
              device_kind: str | None = None) -> str:
    """Cache key: device kind + backend + op + dtype + bucketed shape.

    n and m are pow2-bucketed so e.g. n = 250k and n = 262144 resolve to
    the same plan (the roofline is smooth in n); d, the accumulator and
    the precision REQUEST are exact (they change the per-step op mix).
    A joint-resolution request keys as "auto" — the entry then carries the
    chosen precision, separate from any explicitly-pinned entries.
    """
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    dt = jnp.dtype(dtype).name
    return "/".join([device_kind.replace(" ", "_"), backend, op, dt,
                     f"n{_bucket(n)}", f"m{_bucket(m)}", f"d{int(d)}",
                     accumulator, f"px_{precision}"])


# ------------------------------------------------------------------ model --

def _step_costs(op: str, tile: int, m: int, d: int,
                dtype_bytes: int) -> tuple[float, float, float]:
    """(matmul flops, other flops, working-set bytes) of ONE `tile`-row step.

    gram:    (tile, m) kernel slab build (~d+const flops/entry through the
             augmented-GEMM distance) + the (m, m) syrk + (m,) gemv — the
             syrk is the PRECISION-SCALABLE matmul term (the bf16 split
             runs it at the MXU bf16 rate x a words^2-ish partial count);
    predict: slab build + (tile, m) x (m,) gemv;
    deposit: O(2^d) stencil flops per point, no MXU term; the working set
             is the corner stream plus the resident (m,)^d grid.
    """
    if op == "gram":
        mat = 2.0 * tile * m * m
        rest = 2.0 * tile * m * (d + 2) + 12.0 * tile * m + 2.0 * tile * m
        ws = tile * (m + d) * dtype_bytes + 2 * m * m * dtype_bytes
    elif op == "predict":
        mat = 0.0
        rest = 2.0 * tile * m * (d + 2) + 12.0 * tile * m + 2.0 * tile * m
        ws = tile * (m + d) * dtype_bytes
    elif op == "deposit":
        corners = 2 ** d
        mat = 0.0
        rest = 24.0 * tile * corners
        ws = tile * (corners + d) * dtype_bytes \
            + min(float(m) ** d, 16e6) * dtype_bytes
    else:
        raise ValueError(f"unknown op {op!r}; pick from {OPS}")
    return mat, rest, float(ws)


def model_seconds(op: str, tile: int, n: int, m: int, d: int, *,
                  dtype_bytes: int = 4,
                  spec: DeviceSpec | None = None,
                  precision: str = "fp32") -> float:
    """Analytic whole-stream seconds for one tile choice (ranking only).

    Per step: max(compute, memory) roofline + the fixed step overhead; a
    slab that outgrows `spec.cache_bytes` degrades the compute rate
    proportionally (GEMM panels start streaming from main memory — the
    empirically dominant effect behind the 2x tile swing on CPU).  The
    matmul share of the flops is scaled by the device's per-precision
    compute ceiling (`DeviceSpec.matmul_cost`): < 1 where the bf16 split's
    partial matmuls ride a faster MXU path, > 1 where bf16 emulation is a
    slowdown (CPU) — which is how ``precision=None`` resolution picks fp32
    on CPU and the split modes on MXU hardware.
    """
    spec = spec or device_spec()
    steps = max(1, -(-n // tile))
    mat, rest, ws = _step_costs(op, min(tile, n), m, d, dtype_bytes)
    flops = rest + mat * spec.matmul_cost(precision)
    spill = max(1.0, ws / spec.cache_bytes)
    t_compute = flops / spec.peak_flops * spill
    t_memory = ws / spec.mem_bw
    return steps * (max(t_compute, t_memory) + spec.step_overhead)


def candidate_tiles(op: str, n: int, m: int, d: int, *,
                    dtype_bytes: int = 4,
                    spec: DeviceSpec | None = None,
                    precision: str = "fp32") -> list[int]:
    """Model-ranked pow2 tile ladder (best first), bounded by n and memory.

    The top rung is the pow2-ceil of n (a one-shot slab), so small-n calls
    degenerate to a single whole-array candidate — exactly the historical
    un-tiled behavior, resolved in microseconds.
    """
    spec = spec or device_spec()
    hi = min(_bucket(n), MAX_TILE) if n > 0 else MIN_TILE
    lo = min(MIN_TILE, hi)
    ladder, t = [], lo
    while t <= hi:
        if t * max(m, 1) * dtype_bytes <= _SLAB_BYTES_CAP:
            ladder.append(t)
        t *= 2
    if not ladder:
        ladder = [lo]
    if _bucket(n) > MAX_TILE and MAX_TILE not in ladder:
        ladder.append(MAX_TILE)
    ladder.sort(key=lambda c: model_seconds(op, c, n, m, d,
                                            dtype_bytes=dtype_bytes,
                                            spec=spec, precision=precision))
    return ladder


# ---------------------------------------------------------------- measure --

def _bench(fn: Callable[[], object], reps: int = 3) -> float:
    """Best-of-`reps` wall-clock of an already-warm compiled callable."""
    fn()                                  # compile + warm (excluded)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _measure_tile(op: str, tile: int, n: int, m: int, d: int, dtype,
                  accumulator: str, precision: str = "fp32") -> float:
    """Whole-stream seconds for one candidate, extrapolated from a short
    synthetic stream (<= a few tiles of rows) — candidates are compared on
    identical data/step counts, so the extrapolation cancels out of the
    argmin."""
    n_s = int(min(n, max(4 * tile, 16384)))
    n_s = max(n_s, tile) if tile <= n else n_s
    key = jax.random.PRNGKey(0)
    if op == "deposit":
        from repro.core import kde
        g = max(int(m), 4)
        pts = jax.random.uniform(key, (n_s, d), dtype)
        lo = jnp.zeros((d,), dtype)
        spacing = jnp.full((d,), 1.0 / (g - 1), dtype)
        fn = lambda: kde.scatter_cic(pts, lo, spacing, g, tile=tile,  # noqa: E731
                                     accumulator=accumulator)
    else:
        from repro.core import kernels as core_kernels
        from repro.core import nystrom
        kern = core_kernels.Matern(nu=1.5, lengthscale=1.0)
        x = jax.random.normal(key, (n_s, d), dtype)
        xm = x[: min(int(m), n_s)]
        if op == "gram":
            w = jnp.ones((n_s,), dtype)
            fn = jax.jit(lambda: nystrom.scan_normal_eq(
                kern, x, xm, w, tile=tile, accumulator=accumulator,
                precision=precision))
        else:
            beta = jnp.zeros((xm.shape[0],), dtype)
            fit = nystrom.NystromFit(beta=beta, landmarks=xm,
                                     landmark_idx=jnp.arange(xm.shape[0]),
                                     lam=1e-3)
            fn = jax.jit(lambda: nystrom.predict_streaming(
                kern, fit, x, tile=tile))
    per_stream = _bench(fn)
    steps_sampled = max(1, -(-n_s // tile))
    steps_total = max(1, -(-n // tile))
    return per_stream / steps_sampled * steps_total


# --------------------------------------------------------------- plan_for --

_LAST: dict[str, Plan] = {}


def last_plans(reset: bool = False) -> dict[str, Plan]:
    """The plan `plan_for` most recently returned for each op (what a stage
    that just ran resolved, with its ``source``); ``reset`` forgets them."""
    out = dict(_LAST)
    if reset:
        _LAST.clear()
    return out


def plan_for(op: str, n: int, m: int, d: int, *, dtype=jnp.float32,
             backend: str = "xla", accumulator: str = "plain",
             precision: str | None = "fp32",
             measure: bool | None = None) -> Plan:
    """Resolve the execution plan for one streamed op at one shape.

    Cache first (warm runs never tune); then the roofline model ranks the
    ladder; when measurement is enabled (``measure=True``, the
    ``measured()`` context, or ``REPRO_AUTOTUNE=1``) AND legal (not under a
    trace; Pallas plans only measure on a real TPU), the top
    `MEASURE_TOP_K` candidates are micro-benchmarked and the argmin wins.
    A measured entry permanently shadows a model entry for its bucket.

    ``precision`` pins the Gram-contraction mode the plan is ranked for.
    ``precision=None`` on the gram op resolves the (tile, precision) pair
    JOINTLY over `AUTO_PRECISIONS`: the candidate set is the cross product
    of the tile ladder with the eligible modes, ranked by the
    per-precision roofline (and micro-benchmarked as pairs when
    measurement is on).  Non-gram ops have no precision-scalable matmul
    and always plan as "fp32".
    """
    plan = _resolve(op, n, m, d, dtype=dtype, backend=backend,
                    accumulator=accumulator, precision=precision,
                    measure=measure)
    _LAST[op] = plan
    return plan


def _resolve(op: str, n: int, m: int, d: int, *, dtype, backend: str,
             accumulator: str, precision: str | None,
             measure: bool | None) -> Plan:
    if op not in OPS:
        raise ValueError(f"unknown op {op!r}; pick from {OPS}")
    if precision is not None and precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}; "
                         f"pick from {PRECISIONS} or None")
    if op != "gram":
        precision = "fp32"
    n, m, d = int(n), int(m), int(d)
    if n <= 0 or m <= 0:
        return Plan(op=op, tile=DEFAULT_TILE, precision=precision or "fp32")
    _load_disk()
    key = shape_key(op, n, m, d, dtype=dtype, backend=backend,
                    accumulator=accumulator,
                    precision=precision if precision is not None else "auto")
    want = (measuring() if measure is None else measure) and _can_measure()
    if backend == "pallas" and jax.default_backend() != "tpu":
        want = False   # interpret-mode timings are meaningless
    entry = _MEMORY.get(key)
    if entry is not None and (entry["source"] == "measured" or not want):
        return Plan(op=op, tile=int(entry["tile"]),
                    bm=int(entry.get("bm", DEFAULT_BM)),
                    bn=int(entry.get("bn", DEFAULT_BN)), source="cache",
                    precision=str(entry.get("precision", "fp32")))

    dtype_bytes = jnp.dtype(dtype).itemsize
    precs = AUTO_PRECISIONS if precision is None else (precision,)
    ladder = candidate_tiles(op, n, m, d, dtype_bytes=dtype_bytes)
    pairs = [(t, p) for p in precs for t in ladder]
    pairs.sort(key=lambda tp: model_seconds(op, tp[0], n, m, d,
                                            dtype_bytes=dtype_bytes,
                                            precision=tp[1]))
    (tile, prec), source, tuning_s = pairs[0], "model", 0.0
    if want:
        t0 = time.perf_counter()
        timed = {tp: _measure_tile(op, tp[0], n, m, d, dtype, accumulator,
                                   precision=tp[1])
                 for tp in pairs[:MEASURE_TOP_K]}
        tile, prec = min(timed, key=timed.get)
        source, tuning_s = "measured", time.perf_counter() - t0
    plan = Plan(op=op, tile=tile, source=source, tuning_seconds=tuning_s,
                precision=prec)
    _MEMORY[key] = {"tile": plan.tile, "bm": plan.bm, "bn": plan.bn,
                    "precision": plan.precision, "source": source}
    _save_disk()
    return plan
