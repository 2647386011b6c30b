"""Sweep the offered rate of a serving cell to find its knee, in one process.

  python3 -m bench.knee --workload <name> --rates 2000,5000,... \
      [--seconds 8] [--seed 1]

Set-up runs once; then one open-loop window per rate, in the order given.
A rate is sustained when the served rows keep up with the offered rows and
the latency of the window's last quarter of requests is not above that of
its first quarter by more than the first quarter's own p99: no backlog
grows.  One JSON line per rate.  Like the benchmark itself it refuses to
run off a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from bench import run as bench_run


def summary(rate: float, seconds: float, sizes, result: dict) -> dict:
    lat = result["latency_s"]
    q = len(lat) // 4
    first = np.quantile(lat[:q], [0.5, 0.99], method="inverted_cdf")
    last = np.quantile(lat[-q:], [0.5, 0.99], method="inverted_cdf")
    offered = float(np.sum(sizes)) / seconds
    served = result["metrics"]["serve_rows_per_s"]
    return {"rate_per_s": rate, "requests": int(len(lat)),
            "failed": result["failed"],
            "p50_ms": 1e3 * float(np.quantile(lat, 0.5,
                                              method="inverted_cdf")),
            "p99_ms": result["metrics"]["serve_p99_ms"],
            "first_quarter_p50_p99_ms": [1e3 * float(v) for v in first],
            "last_quarter_p50_p99_ms": [1e3 * float(v) for v in last],
            "offered_rows_per_s": offered, "served_rows_per_s": served,
            "lag_p99_ms": 1e3 * float(np.quantile(
                result["lags_s"], 0.99, method="inverted_cdf")),
            "rows_per_batch": (result["engine"]["rows"]
                               / max(result["engine"]["batches"], 1)),
            "sustained": bool(served >= 0.98 * offered
                              and last[0] <= first[0] + first[1])}


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(bench_run.ROOT, "src"))
    from bench import loops

    files = bench_run.load_cell(args.workload)
    devices, _, _ = bench_run.device_check(int(files["cell"]["chips"]))
    bench_run.configure(files["config"])
    rates = [float(r) for r in args.rates.split(",")]
    mix = dict(files["traffic"], rate_per_s=rates[0])
    loop = loops.make(loops.deployment(files["config"], args.seed), mix,
                      devices)
    loop.setup(args.seconds)
    for rate in rates:
        loop.prepare(args.seconds, rate)
        result = loop.window(args.seconds)
        print(json.dumps(summary(rate, args.seconds, loop.sizes, result)),
              flush=True)
    loop.engine.stop()


if __name__ == "__main__":
    main()
