"""Serving launcher: freeze a fitted SA-KRR pipeline and drive the
microbatching predict engine under concurrent synthetic load.

  PYTHONPATH=src python -m repro.launch.serve --smoke
  PYTHONPATH=src python -m repro.launch.serve --n 16384 --m 1024 \
      --requests 4096 --producers 4 --window 64

Flow: fit on the paper's bimodal design -> `ServableKRR.freeze` ->
save/load round-trip (asserting bit-parity with the live pipeline) ->
`ServingEngine` -> warm sequential single-row latency, then >= 4 producer
threads each keeping a sliding window of requests in flight.  Prints p50 /
p99 latency and sustained rows/sec.  `benchmarks/bench_serving.py` reuses
the load helpers here and adds the JSON trajectory record.
"""

from __future__ import annotations

import argparse
import os
import tempfile
import threading
import time
from collections import deque

import jax
import numpy as np

from repro.data import krr_data
from repro.launch import compile_cache
from repro.pipeline import PipelineConfig, SAKRRPipeline
from repro.serving import ServableKRR, ServingEngine


# ----------------------------------------------------------------- fitting --
def fit_and_freeze(n: int, m: int, *, d: int = 3, seed: int = 0,
                   tile: int | None = None) -> tuple[SAKRRPipeline,
                                                     ServableKRR]:
    """Fit the bimodal workload, freeze, and save/load round-trip the
    artifact (so every launcher run exercises the persistence path)."""
    data = krr_data.bimodal(jax.random.PRNGKey(seed), n, d=d)
    cfg = PipelineConfig(num_landmarks=m, tile=tile, seed=seed)
    pipe = SAKRRPipeline(cfg).fit(data.x, data.y)
    frozen = ServableKRR.freeze(pipe)
    with tempfile.TemporaryDirectory() as td:
        art = ServableKRR.load(frozen.save(os.path.join(td, "model.npz")))
    return pipe, art


def make_queries(n_rows: int, d: int, seed: int) -> np.ndarray:
    """Fresh draws from the same bimodal input law (includes the far mode)."""
    return np.asarray(
        krr_data.bimodal(jax.random.PRNGKey(seed), max(n_rows, 2), d=d).x
    )[:n_rows]


# -------------------------------------------------------------------- load --
def sequential_latency(engine: ServingEngine, queries: np.ndarray,
                       rows_per_request: int = 1) -> list[float]:
    """One-request-at-a-time round trips (the latency floor / throughput
    baseline): submit, block, record, repeat."""
    lats = []
    for i in range(0, len(queries), rows_per_request):
        chunk = queries[i:i + rows_per_request]
        t0 = time.perf_counter()
        engine.predict(chunk)
        lats.append(time.perf_counter() - t0)
    return lats


def _producer(engine: ServingEngine, chunks: list[np.ndarray], window: int,
              lats: list[float]) -> None:
    inflight: deque = deque()
    for chunk in chunks:
        if len(inflight) >= window:
            t0, fut = inflight.popleft()
            fut.result()
            lats.append(time.perf_counter() - t0)
        inflight.append((time.perf_counter(), engine.submit(chunk)))
    while inflight:
        t0, fut = inflight.popleft()
        fut.result()
        lats.append(time.perf_counter() - t0)


def concurrent_load(engine: ServingEngine, queries: np.ndarray, *,
                    producers: int, window: int,
                    rows_per_request: int = 1) -> tuple[list[float], float]:
    """`producers` threads, each with a sliding `window` of requests in
    flight (open-loop-ish arrival: the engine sees real queue depth, not
    one request per thread).  Returns (per-request latencies, wall secs)."""
    chunks = [queries[i:i + rows_per_request]
              for i in range(0, len(queries), rows_per_request)]
    shares = [chunks[p::producers] for p in range(producers)]
    lat_lists: list[list[float]] = [[] for _ in range(producers)]
    threads = [threading.Thread(target=_producer,
                                args=(engine, shares[p], window,
                                      lat_lists[p]))
               for p in range(producers)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return [l for ls in lat_lists for l in ls], wall


def pct(lats: list[float], q: float) -> float:
    return float(np.percentile(np.asarray(lats), q)) if lats else float("nan")


# -------------------------------------------------------------------- main --
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small fast run for CI")
    ap.add_argument("--n", type=int, default=16384, help="training rows")
    ap.add_argument("--m", type=int, default=1024, help="landmarks")
    ap.add_argument("--d", type=int, default=3)
    ap.add_argument("--requests", type=int, default=2048)
    ap.add_argument("--producers", type=int, default=4)
    ap.add_argument("--window", type=int, default=64,
                    help="in-flight requests per producer")
    ap.add_argument("--rows-per-request", type=int, default=1)
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--tile", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.configure()
    if args.smoke:
        args.n, args.m = min(args.n, 2048), min(args.m, 128)
        args.requests, args.window = min(args.requests, 192), 16

    t0 = time.perf_counter()
    pipe, art = fit_and_freeze(args.n, args.m, d=args.d, seed=args.seed,
                               tile=args.tile)
    fit_s = time.perf_counter() - t0
    queries = make_queries(args.requests * args.rows_per_request, args.d,
                           args.seed + 1)
    live = np.asarray(pipe.predict(jax.numpy.asarray(queries[:64])))
    loaded = np.asarray(art.predict(jax.numpy.asarray(queries[:64])))
    bitpar = bool(np.array_equal(live, loaded))
    print(f"fit n={args.n} m={args.m} d={args.d}: {fit_s:.2f}s  "
          f"save/load bit-parity={bitpar}")
    if not bitpar:
        raise SystemExit("artifact round-trip is NOT bit-equal to the "
                         "live pipeline predict")

    with ServingEngine(art, max_batch=args.max_batch) as eng:
        eng.warm()
        seq = sequential_latency(eng, queries[:min(128, len(queries))],
                                 args.rows_per_request)
        print(f"sequential single-request: p50={pct(seq, 50) * 1e3:.2f}ms "
              f"p99={pct(seq, 99) * 1e3:.2f}ms")
        lats, wall = concurrent_load(eng, queries,
                                     producers=args.producers,
                                     window=args.window,
                                     rows_per_request=args.rows_per_request)
        rows = len(queries)
        print(f"concurrent x{args.producers} (window {args.window}): "
              f"{rows / wall:.0f} rows/s  p50={pct(lats, 50) * 1e3:.2f}ms "
              f"p99={pct(lats, 99) * 1e3:.2f}ms  wall={wall:.2f}s")
        st = eng.stats
        print(f"engine: batches={st.batches} compiles={st.compiles} "
              f"occupancy={st.occupancy:.2f}")


if __name__ == "__main__":
    main()
