"""Bring-up smoke run of the SA-leverage Nystrom pipeline on a TPU.

  python chip_smoke.py             # one chip: fit/evaluate, Pallas vs XLA,
                                   # compiled kernels, serving
  python chip_smoke.py --chips 4   # row-sharded fit on a 4-chip ("data",)
                                   # mesh against the one-chip fit

One process holds the chip and starts no children.  Without a TPU it exits
non-zero and prints no result.  Every phase raises on a mismatch; the last
line of stdout is ``{"ok": true, "device": {...}}``.  Stage seconds printed
here are smoke timings of one cold run (compilation included), not a
benchmark.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import tuning  # noqa: E402
from repro.core import kde, kernels, nystrom  # noqa: E402
from repro.data import krr_data  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.pipeline import PipelineConfig, SAKRRPipeline  # noqa: E402
from repro.roofline import analysis as roofline  # noqa: E402
from repro.serving import ServableKRR, ServingEngine  # noqa: E402

# Fig. 1 design at the standing size of examples/krr_largescale.py.
N, D, M, NU, TILE = 1_000_000, 3, 1024, 1.5, 16384
PARITY_ROWS = 65_536
REQUESTS = 300
MAX_RISK = 1e-3          # n = 1e6 risk bar against f_star
RISK_RATIO = 1.5         # Pallas fit vs the XLA-backend fit of the same draw
# Pallas vs XLA on the chip (relative): the deposit sums the same f32 CIC
# weights in another order; Gram and predict also differ in how each
# backend rounds the MXU contraction and evaluates exp/sqrt.
PARITY_TOL = {"gram": 1e-3, "gram_rhs": 1e-3, "binned_scatter": 1e-4,
              "predict": 1e-3}
# Sharded vs one-chip predictions: the psum reorders the Gram sums, which
# the whitened solve amplifies (the forced-host-device parity tests use
# the same rtol/atol).
SHARD_RTOL, SHARD_ATOL = 2e-2, 2e-3


def _say(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Backend-compile seconds and persistent-cache hits/misses, from JAX's
    monitoring events (a persistent-cache hit still reports its load)."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# ---------------------------------------------------------------- phases --

def device_check(chips: int = 1) -> dict:
    """The platform must be a TPU with ``chips`` devices and a known
    roofline spec; raises SystemExit otherwise."""
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    _say(f"device: platform={dev['platform']} kind={dev['kind']} "
         f"count={dev['count']}")
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found platform "
                         f"{dev['platform']!r}")
    if dev["count"] != chips:
        raise SystemExit(f"chip_smoke: expected {chips} TPU device(s), "
                         f"found {dev['count']}")
    spec = roofline.device_spec(dev["kind"])
    _say(f"device spec: {spec.source}")
    return dev


def _stage_plans(cfg: PipelineConfig, plans: dict) -> dict:
    """What each stage resolved: backend, then bm/bn (Pallas) or tile
    (XLA), Gram precision, and where the plan came from."""
    backend = dispatch.resolve(None if cfg.backend == "auto" else cfg.backend)
    out = {}
    for stage, op, pinned in (("kde", "deposit", cfg.kde_tile),
                              ("solve", "gram", cfg.tile),
                              ("predict", "predict", cfg.tile)):
        plan = plans.get(op)
        rec = {"backend": backend}
        if backend == "pallas" and op != "predict":
            rec.update(bm=plan.bm, source=plan.source)
            if op == "gram":
                rec.update(bn=plan.bn, precision=plan.precision)
        elif pinned is not None:
            rec.update(tile=pinned, source="explicit")
        else:
            rec.update(tile=plan.tile, source=plan.source)
        if op == "gram" and "precision" not in rec:
            rec["precision"] = cfg.precision or (
                plan.precision if plan is not None else "fp32")
        out[stage] = rec
    return out


def fit_and_evaluate(n: int = N, d: int = D, m: int = M, tile: int = TILE,
                     *, seed: int = 0, max_risk: float = MAX_RISK,
                     expect_backend: str | None = "pallas") -> dict:
    """`SAKRRPipeline.evaluate` on `krr_data.bimodal` through the auto path,
    again with an explicit tile pinned to fp32, and once on the XLA
    backend as the reference; then one `predict` on each fit."""
    data = krr_data.bimodal(jax.random.PRNGKey(seed), n, d=d)
    probe = data.x[:min(n, PARITY_ROWS)]
    runs = {
        "auto": PipelineConfig(nu=NU, num_landmarks=m, seed=seed),
        "fp32_tile": PipelineConfig(nu=NU, num_landmarks=m, tile=tile,
                                    precision="fp32", seed=seed),
        "xla": PipelineConfig(nu=NU, num_landmarks=m, backend="xla",
                              seed=seed),
    }
    out = {}
    for label, cfg in runs.items():
        tuning.last_plans(reset=True)
        pipe = SAKRRPipeline(cfg)
        scores = pipe.evaluate(data.x, data.y, f_star=data.f_star)
        pred = pipe.predict(probe)
        if pred.shape != (probe.shape[0],) or not bool(
                jnp.all(jnp.isfinite(pred))):
            raise AssertionError(f"{label}: predict gave {pred.shape}, "
                                 f"finite={bool(jnp.all(jnp.isfinite(pred)))}")
        plans = _stage_plans(cfg, tuning.last_plans())
        seconds = {k: round(v, 4) for k, v in pipe.seconds.items()}
        _say(f"[{label}] n={n} d={d} m={m} risk={scores['risk']:.6e} "
             f"mse={scores['mse']:.6e}")
        for stage, rec in plans.items():
            _say(f"[{label}]   {stage}: {rec}")
        _say(f"[{label}]   smoke timings, not a benchmark (s): {seconds}")
        out[label] = {"pipe": pipe, "scores": scores, "plans": plans,
                      "data": data}
    ref = out["xla"]["scores"]["risk"]
    for label in ("auto", "fp32_tile"):
        _say(f"[{label}] risk / XLA-backend risk = "
             f"{out[label]['scores']['risk'] / ref:.4f}")
    for label in ("auto", "fp32_tile"):
        risk = out[label]["scores"]["risk"]
        if expect_backend is not None:
            got = {s: p["backend"] for s, p in out[label]["plans"].items()}
            if set(got.values()) != {expect_backend}:
                raise AssertionError(f"{label}: stages resolved {got}, "
                                     f"expected {expect_backend}")
        if not risk < max_risk:
            raise AssertionError(f"{label}: risk {risk:.3e} >= {max_risk}")
        if not risk <= RISK_RATIO * ref:
            raise AssertionError(f"{label}: risk {risk:.3e} > {RISK_RATIO} x "
                                 f"XLA-backend risk {ref:.3e}")
    return out


def _rel(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def kernel_parity(pipe: SAKRRPipeline, x, y, *, rows: int = PARITY_ROWS,
                  grid_size: int | None = None) -> dict:
    """Pallas against XLA on the chip for the three dispatched kernels, on
    the first ``rows`` rows of the fitted draw."""
    xs, ys = x[:rows], y[:rows]
    fit = pipe.state.fit
    kern = pipe.kernel
    d = xs.shape[1]
    g = grid_size or kde.default_grid_size(d)
    h = kde.scott_bandwidth(xs)
    lo, hi = kde.binned_bounds(xs, xs, h)
    spacing = (hi - lo) / (g - 1)
    got = {}
    for backend in ("pallas", "xla"):
        gram = dispatch.gram_accumulate(kern, xs, fit.landmarks, ys,
                                        backend=backend, precision="fp32")
        grid = dispatch.binned_scatter(xs, lo, spacing, g, backend=backend)
        pred = nystrom.predict_streaming(kern, fit, xs, backend=backend)
        got[backend] = jax.device_get((gram, grid, pred))
    (gp, rp), grid_p, pred_p = got["pallas"]
    (gx, rx), grid_x, pred_x = got["xla"]
    err = {"gram": _rel(gp, gx), "gram_rhs": _rel(rp, rx),
           "binned_scatter": _rel(grid_p, grid_x),
           "predict": _rel(pred_p, pred_x)}
    for op, e in err.items():
        _say(f"pallas vs xla on {rows} rows: {op} relative error {e:.3e} "
             f"(limit {PARITY_TOL[op]:.0e})")
    for op, e in err.items():
        if not e <= PARITY_TOL[op]:
            raise AssertionError(f"{op}: Pallas vs XLA relative error {e:.3e}"
                                 f" > {PARITY_TOL[op]}")
    return err


def compiled_kernels(rows: int = PARITY_ROWS, m: int = M, d: int = D,
                     grid_size: int | None = None, *, sharding=None) -> dict:
    """Compile the Gram, deposit and pairwise Pallas kernels with
    ``interpret=False`` and count the ``tpu_custom_call`` ops in each
    compiled module (``sharding`` places the abstract inputs, e.g. on a
    described topology)."""
    from repro.kernels.gram import ops as gram_ops
    from repro.kernels.kde_binned import ops as kb_ops
    from repro.kernels.pairwise import ops as pw_ops

    g = grid_size or kde.default_grid_size(d)
    f32 = jnp.float32

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, f32, sharding=sharding)

    lowered = {
        "gram": jax.jit(lambda x, y, w: gram_ops.gram(
            x, y, w, nu=NU, interpret=False)).lower(
                spec((rows, d)), spec((m, d)), spec((rows,))),
        "binned_scatter": jax.jit(lambda p, lo, sp: kb_ops.binned_scatter(
            p, lo, sp, g, interpret=False)).lower(
                spec((rows, d)), spec((d,)), spec((d,))),
        "pairwise": jax.jit(lambda x, y: pw_ops.pairwise(
            x, y, nu=NU, interpret=False)).lower(
                spec((rows, d)), spec((m, d))),
    }
    counts = {}
    for name, low in lowered.items():
        counts[name] = low.compile().as_text().count("tpu_custom_call")
        _say(f"compiled {name}: {counts[name]} tpu_custom_call op(s)")
    for name in counts:
        if counts[name] < 1:
            raise AssertionError(f"{name}: no tpu_custom_call in the compiled "
                                 f"module (interpret mode?)")
    return counts


def serving(pipe: SAKRRPipeline, x, *, requests: int = REQUESTS,
            seed: int = 0) -> dict:
    """Freeze the fit, let `ServingEngine` answer single- and multi-row
    requests, and check each answer against `artifact.predict` on the same
    rows, within the f32 forward-error bound of the final (rows, m) x (m,)
    contraction: two sums of the same products may be ordered differently
    when the engine's padded batch and the request alone compile apart."""
    art = ServableKRR.freeze(pipe)
    rng = np.random.default_rng(seed)
    xs = np.asarray(x)
    reqs = []
    for i in range(requests):
        k = 1 if i % 2 == 0 else int(rng.integers(2, 33))
        at = int(rng.integers(0, xs.shape[0] - k))
        reqs.append(xs[at] if k == 1 else xs[at:at + k])
    t0 = time.perf_counter()
    with ServingEngine(art, max_batch=256) as engine:
        futures = [engine.submit(r) for r in reqs]
        served = [np.atleast_1d(f.result(timeout=600)) for f in futures]
    serve_s = time.perf_counter() - t0
    beta = np.abs(np.asarray(art.beta, np.float64))
    eps = float(np.finfo(np.float32).eps)
    worst, exact, over = 0.0, 0, 0
    for rows, got in zip(reqs, served):
        rows2 = np.atleast_2d(rows)
        want = np.asarray(art.predict(jnp.asarray(rows2)))
        k_abs = np.abs(np.asarray(kernels.kernel_matrix(
            art.kernel, jnp.asarray(rows2), art.landmarks), np.float64))
        bound = art.num_landmarks * eps * (k_abs @ beta) + 4 * eps * np.abs(
            want)
        diff = np.abs(got.astype(np.float64) - want)
        over += int(got.shape != want.shape or not np.all(diff <= bound))
        worst = max(worst, float(diff.max()))
        exact += int(np.array_equal(got, want))
    rows_total = sum(np.atleast_2d(r).shape[0] for r in reqs)
    st = engine.stats
    _say(f"serving: {len(reqs)} requests ({rows_total} rows) answered, "
         f"{exact} bit-equal to artifact.predict, max |diff| {worst:.3e}, "
         f"{over} over the bound; batches={st.batches} "
         f"compiles={st.compiles} occupancy={st.occupancy:.3f}; "
         f"smoke wall {serve_s:.3f}s")
    if over:
        raise AssertionError(f"{over} served answer(s) differ from "
                             f"artifact.predict beyond the f32 bound")
    return {"requests": len(reqs), "exact": exact, "max_diff": worst}


def sharded_fit(n: int = N, d: int = D, m: int = M, *, seed: int = 0,
                chips: int = 4, max_risk: float = MAX_RISK) -> dict:
    """The row-sharded evaluate on a ("data",) mesh over ``chips`` devices
    against the one-chip evaluate of the same draw, in this process."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed import sharding as shd
    from repro.launch import mesh as mesh_lib

    devices = jax.devices()[:chips]
    data = krr_data.bimodal(jax.random.PRNGKey(seed), n, d=d)
    cfg = PipelineConfig(nu=NU, num_landmarks=m, seed=seed)
    probe = data.x[:min(n, PARITY_ROWS)]

    one = SAKRRPipeline(cfg)
    risk_one = one.evaluate(data.x, data.y, f_star=data.f_star)["risk"]
    pred_one = np.asarray(one.predict(probe))

    mesh = mesh_lib.make_local_mesh("data", devices)
    rows = NamedSharding(mesh, P("data"))
    x = jax.device_put(data.x, NamedSharding(mesh, P("data", None)))
    y = jax.device_put(data.y, rows)
    f_star = jax.device_put(data.f_star, rows)
    xp = jax.device_put(probe, NamedSharding(mesh, P("data", None)))
    shares = {str(s.device): s.data.shape[0] for s in x.addressable_shards}
    _say(f"rows per chip: {shares}")
    with shd.activate(mesh):
        sh = SAKRRPipeline(cfg)
        risk_sh = sh.evaluate(x, y, f_star=f_star)["risk"]
        pred_sh = sh.predict(xp)
    placed = {"x": x, "densities": sh.state.densities,
              "leverage.probs": sh.state.leverage.probs,
              "predictions": pred_sh}
    for name, a in placed.items():
        devs = a.sharding.device_set
        per = sorted({s.data.shape[0] for s in a.addressable_shards})
        _say(f"{name}: on {len(devs)} device(s), shard rows {per} of "
             f"{a.shape[0]}")
        if len(devs) != chips or max(per) >= a.shape[0]:
            raise AssertionError(f"{name} is not row-sharded over the "
                                 f"{chips} chips: {a.sharding}")
    pred_sh = np.asarray(pred_sh)
    diff = float(np.max(np.abs(pred_sh - pred_one)))
    limit = SHARD_RTOL * float(np.max(np.abs(pred_one))) + SHARD_ATOL
    _say(f"risk: one chip {risk_one:.6e}, {chips} chips {risk_sh:.6e}")
    _say(f"max |pred {chips} chips - pred one chip| = {diff:.3e} "
         f"(limit {limit:.3e})")
    if not diff <= limit:
        raise AssertionError(f"sharded predictions differ by {diff:.3e} > "
                             f"{limit:.3e}")
    for label, r in (("one chip", risk_one), (f"{chips} chips", risk_sh)):
        if not r < max_risk:
            raise AssertionError(f"{label}: risk {r:.3e} >= {max_risk}")
    return {"risk_one": risk_one, "risk_sharded": risk_sh, "max_diff": diff,
            "shares": shares}


# ------------------------------------------------------------------ main --

def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the row-sharded fit against one chip")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    dev = device_check(args.chips)
    _say(f"compile cache: {compile_cache.configure()}")
    clock = CompileClock()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_plans_") as plans:
        # plans are resolved afresh in this run, never read from a file
        # outside the checkout
        os.environ["REPRO_TUNE_CACHE"] = os.path.join(plans, "autotune.json")
        if args.chips == 1:
            runs = fit_and_evaluate()
            auto = runs["auto"]
            kernel_parity(auto["pipe"], auto["data"].x, auto["data"].y)
            compiled_kernels()
            serving(auto["pipe"], auto["data"].x)
        else:
            sharded_fit(chips=args.chips)
    _say(f"compile: {clock.seconds:.3f}s backend compile, persistent cache "
         f"{clock.hits} hit(s) / {clock.misses} miss(es); total wall "
         f"{time.perf_counter() - t_start:.3f}s")
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
