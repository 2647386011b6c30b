"""Cells at a small size, for CPU rehearsals.

A cell comes from BENCHMARK.json, or from ``pending.json`` beside this file:
the cells whose configuration, traffic and readers are in ``bench/`` but
which BENCHMARK.json does not hold yet (PERF.md, Open questions).
"""

import json
import os

from bench import run as bench_run

PENDING = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "pending.json")
# rows and landmarks at which a sound float32 run on the CPU reads within
# the cells' limits (PERF.md, "How correct is decided")
SIZES = {"fig1_matern": (65536, 128), "fig3_gaussian": (65536, 96),
         "fig1_matern_x4": (65536, 128)}


def manifest(workload: str) -> str:
    with open(bench_run.MANIFEST) as f:
        held = {w["name"] for w in json.load(f)["workloads"]}
    return bench_run.MANIFEST if workload in held else PENDING


def cell(workload: str, size: tuple | None = None) -> dict:
    """`bench.run.load_cell`, at the rehearsal size or at ``size`` (rows,
    landmarks); a pending fit cell is judged by the `fig1_matern.fit`
    limits."""
    files = bench_run.load_cell(workload, manifest(workload))
    n, m = size or SIZES[files["cell"]["config"]]
    files["config"].update(n=n, m=m)
    if files["traffic"]["loop"] == "serve":
        files["traffic"].update(rate_per_s=400.0, query_pool_rows=32768,
                                check_requests=200)
    elif not files["limits"]:
        files["limits"] = bench_run.load_cell("fig1_matern.fit")["limits"]
    return files
