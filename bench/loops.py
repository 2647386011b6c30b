"""The general traffic driver: what a mix file under ``bench/traffic/`` names.

A mix's ``"loop"`` key picks one of two loops, and its other keys are their
parameters:

* ``"fit"``, closed loop: ``SAKRRPipeline(config).fit(x, y)`` back to back
  on the same device-resident rows, as a user refitting a deployment does.
* ``"serve"``, open loop: requests due on a schedule drawn from the seed
  (``rate_per_s``, ``rows``; Poisson arrivals) are submitted to a
  ``ServingEngine`` over a frozen fit whether or not earlier ones have
  come back; each is timed from its due time.

Both share set-up (inputs made on the device from the seed, one warm-up of
every shape the window uses), the measured window, and ``outputs()``, which
hands what the window produced to the comparison and frees the program's
state.
"""

from __future__ import annotations

import contextlib
import functools
import gc
import math
import time

import jax
import numpy as np

from bench import data


def deployment(config: dict, seed: int) -> dict:
    """Resolve a configuration file into the sizes both the program and the
    reference run: n, d, m, lam, kernel, grid, jitter, sampling seed, and
    the ``PipelineConfig`` arguments (`bench.run.configure` sets the
    file's matrix-product precision)."""
    n, d = int(config["n"]), int(config["d"])
    rule = config["lam_rule"]
    lam = float(rule["scale"]) * n ** float(rule["power"])
    words = data.seed_words(seed)
    kern = dict(config["kernel"])
    if kern["kind"] == "gaussian" and "sigma_rule" in kern:
        r = kern.pop("sigma_rule")
        kern["sigma"] = float(r["scale"]) * n ** float(r["power"])
    pipeline = dict(kernel_kind=kern["kind"], num_landmarks=int(config["m"]),
                    lam=lam, kde_grid_size=int(config["grid_size"]),
                    jitter=float(config["jitter"]),
                    precision=config["gram_precision"], seed=words[1])
    if kern["kind"] == "matern":
        pipeline.update(nu=float(kern["nu"]),
                        lengthscale=float(kern["lengthscale"]))
    else:
        pipeline.update(sigma=float(kern["sigma"]))
    return {"n": n, "d": d, "m": int(config["m"]), "lam": lam,
            "kernel": kern, "law": config["law"],
            "grid_size": int(config["grid_size"]),
            "jitter": float(config["jitter"]), "chips": int(config["chips"]),
            "data_key": words[0], "sample_seed": words[1],
            "query_key": words[2], "schedule_seed": words[3],
            "pipeline": pipeline}


def settle() -> None:
    """End of set-up: collect, and move what set-up made out of the
    collector's reach, so that the window's collections stay small."""
    gc.collect()
    gc.freeze()


def _host(a) -> np.ndarray:
    return np.asarray(jax.device_get(a))


class _Loop:
    def __init__(self, spec: dict, traffic: dict, devices: list):
        from repro.pipeline import PipelineConfig

        self.spec = spec
        self.traffic = traffic
        self.devices = devices
        self.pcfg = PipelineConfig(**spec["pipeline"])
        self.mesh = None
        if len(devices) > 1:
            from repro.launch import mesh as mesh_lib
            self.mesh = mesh_lib.make_local_mesh("data", devices)

    def scope(self):
        """The program's mesh scope: row-sharded under a multi-chip mesh."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.distributed import sharding as shd
        return shd.activate(self.mesh)

    def make_data(self):
        s = self.spec
        key = jax.random.PRNGKey(s["data_key"])
        shard = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            shard = (NamedSharding(self.mesh, P("data", None)),
                     NamedSharding(self.mesh, P("data")))
        with jax.default_device(self.devices[0]):
            self.x, self.y, self.f_star = data.dataset(
                key, s["law"], s["n"], s["d"], row_sharding=shard)
        jax.block_until_ready((self.x, self.y, self.f_star))

    def fit_once(self):
        from repro.pipeline import SAKRRPipeline

        with self.scope():
            pipe = SAKRRPipeline(self.pcfg).fit(self.x, self.y)
            jax.block_until_ready(pipe.state.fit.beta)
        return pipe

    def reference_inputs(self):
        """x, y, f_star on one device, for the reference."""
        dev = self.devices[0]
        return tuple(jax.device_put(a, dev)
                     for a in (self.x, self.y, self.f_star))


class FitLoop(_Loop):
    def setup(self, seconds: float) -> None:
        del seconds
        self.make_data()
        self.last = self.fit_once()
        settle()

    def window(self, seconds: float) -> dict:
        fits = []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self.last = self.fit_once()
            fits.append(dict(self.last.state.seconds))
        elapsed = time.perf_counter() - t0
        return {"metrics": {"fit_s": elapsed / len(fits)},
                "attempted": len(fits), "failed": 0, "window_s": elapsed,
                "fits": fits}

    def outputs(self) -> dict:
        from repro.core import accstate

        st = self.last.state
        # the normal equations the solve banked (public through `online`)
        g, rhs = accstate.finalize(self.last.online.solve.acc)
        out = {"gram": _host(g), "rhs": _host(rhs).reshape(-1),
               "densities": _host(st.densities),
               "probs": _host(st.leverage.probs),
               "landmark_idx": _host(st.fit.landmark_idx),
               "weights": _host(st.sample_weights),
               "landmarks": _host(st.fit.landmarks),
               "beta": _host(st.fit.beta)}
        del self.last
        return out


class ServeLoop(_Loop):
    def setup(self, seconds: float) -> None:
        """Freeze the program's fit as users do, then hot-swap in the
        model the benchmark serves: the reference's fit of the same rows,
        so that the comparison reads the serving path alone."""
        from repro.core.nystrom import NystromFit
        from repro.serving import ServableKRR, ServingEngine

        from bench import reference

        self.make_data()
        artifact = ServableKRR.freeze(self.fit_once())
        x, y, _ = self.reference_inputs()
        self.model = reference.fit(x, y, self.spec)
        artifact = artifact.refresh(NystromFit(
            beta=self.model["beta"], landmarks=self.model["landmarks"],
            landmark_idx=self.model["landmark_idx"], lam=self.spec["lam"]))
        self.load_pool()
        eng = self.traffic["engine"]
        self.engine = ServingEngine(artifact, **eng).start()
        top = self.engine._bucket(int(eng["max_batch"])
                                  + int(self.traffic["rows"]["max"]) - 1)
        self.engine.warm(tuple(b for b in (2 ** i for i in range(20))
                               if int(eng["min_bucket"]) <= b <= top))
        self.prepare(seconds, float(self.traffic["rate_per_s"]))
        settle()

    def load_pool(self) -> None:
        """Query rows made on the device from the seed, held on the host as
        a client holds its requests."""
        s, law = self.spec, self.spec["law"]
        self.pool = _host(data.queries(
            jax.random.PRNGKey(s["query_key"]),
            n=int(self.traffic["query_pool_rows"]), d=s["d"],
            gamma=float(law["gamma"]), offset=float(law["offset"])))
        self.rng = np.random.default_rng(s["schedule_seed"])

    def prepare(self, seconds: float, rate: float) -> None:
        """The window's schedule: due times, rows per request, and where in
        the query pool each request's rows start.

        Every seed gets the same work: round(rate * seconds) requests whose
        sizes are one fixed multiset (drawn from the mix alone), in an
        order drawn from the seed, due at the sorted uniform times that a
        Poisson process with that many arrivals in the window has.
        """
        rows = self.traffic["rows"]
        count = max(1, round(rate * seconds))
        base = np.random.default_rng(0)
        multi = base.random(count) >= float(rows["p_single"])
        span = base.uniform(math.log(int(rows["min"])),
                            math.log(int(rows["max"]) + 1), count)
        sizes = np.where(multi, np.floor(np.exp(span)).astype(np.int64), 1)
        sizes = self.rng.permutation(np.clip(sizes, 1, int(rows["max"])))
        due = np.sort(self.rng.uniform(0.0, seconds, count))
        pool = self.pool.shape[0]
        starts = np.cumsum(np.concatenate([[0], sizes[:-1]])) % (
            pool - int(rows["max"]))
        self.due, self.sizes, self.starts = due, sizes, starts

    def window(self, seconds: float) -> dict:
        """Submit each request at its due time; a callback stamps its
        completion and keeps the answers the comparison reads, so that the
        generator holds no future past its completion."""
        eng = self.engine
        due, sizes, starts, pool = self.due, self.sizes, self.starts, self.pool
        count = len(due)
        done = np.full(count, np.nan)
        failed = np.zeros(count, bool)
        sent = np.empty(count)
        check = set(self.checked().tolist())
        answers: dict[int, np.ndarray] = {}

        def finished(i: int, fut) -> None:
            done[i] = time.perf_counter()
            if fut.exception() is not None:
                failed[i] = True
            elif i in check:
                answers[i] = np.atleast_1d(fut.result())

        b0, r0 = eng.stats.batches, eng.stats.rows
        t0 = time.perf_counter()
        for i in range(count):
            t_due = t0 + due[i]
            now = time.perf_counter()
            if now < t_due:
                time.sleep(t_due - now)
                now = time.perf_counter()
            sent[i] = now
            eng.submit(pool[starts[i]:starts[i] + sizes[i]]).add_done_callback(
                functools.partial(finished, i))
        close = t0 + seconds
        now = time.perf_counter()
        if now < close:
            time.sleep(close - now)
        b1, r1 = eng.stats.batches, eng.stats.rows
        # answers due in the window may come late: wait a minute past close
        while np.isnan(done).any() and time.perf_counter() < close + 60.0:
            time.sleep(0.005)
        ok = np.isfinite(done) & ~failed
        lat = np.where(ok, done - (t0 + due), np.inf)
        self.answers = {i: answers.get(i) for i in check}
        return {"metrics": {
                    "serve_p99_ms": 1e3 * float(np.quantile(
                        lat, 0.99, method="inverted_cdf")),
                    "serve_rows_per_s": float(np.sum(
                        sizes[ok & (done <= close)])) / seconds},
                "attempted": count, "failed": int(np.sum(~ok)),
                "window_s": seconds,
                "lags_s": sent - (t0 + due), "latency_s": lat,
                "engine": {"batches": b1 - b0, "rows": r1 - r0}}

    def checked(self) -> np.ndarray:
        """Indices, drawn from the seed, of the requests whose answers the
        comparison reads."""
        count = len(self.due)
        take = min(count, int(self.traffic["check_requests"]))
        return np.sort(np.random.default_rng(self.spec["schedule_seed"] + 1)
                       .choice(count, take, replace=False))

    def request_rows(self, i: int) -> np.ndarray:
        return self.pool[self.starts[i]:self.starts[i] + self.sizes[i]]

    def outputs(self) -> dict:
        """The checked requests' rows and what the engine answered for each
        (None where it failed or never came)."""
        self.engine.stop()
        pick = self.checked()
        rows = [self.request_rows(i) for i in pick]
        answers = [self.answers.get(i) for i in pick]
        del self.engine
        return {"rows": rows, "answers": answers}


LOOPS = {"fit": FitLoop, "serve": ServeLoop}


def make(spec: dict, traffic: dict, devices: list) -> _Loop:
    return LOOPS[traffic["loop"]](spec, traffic, devices)

