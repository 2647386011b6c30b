"""Run every paper-table benchmark + the roofline report + the pipeline bench.

  PYTHONPATH=src python -m benchmarks.run                    # all sections
  PYTHONPATH=src python -m benchmarks.run --only fig1
  PYTHONPATH=src python -m benchmarks.run --json BENCH.json  # append records

Sections whose main() accepts a ``json_out`` kwarg (currently: pipeline)
append their records to the --json trajectory file; the others print their
CSV rows as before.
"""

from __future__ import annotations

import argparse
import inspect
import time

from benchmarks import (bench_pipeline, fig1_tradeoff, fig2_curves,
                        fig3_gaussian, roofline_report, table1_racc)
from repro.launch import compile_cache

SECTIONS = {
    "fig1": fig1_tradeoff.main,
    "table1": table1_racc.main,
    "fig2": fig2_curves.main,
    "fig3": fig3_gaussian.main,
    "roofline": roofline_report.main,
    "pipeline": bench_pipeline.main,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=list(SECTIONS))
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="trajectory file for sections that emit records")
    args = ap.parse_args()
    compile_cache.configure()
    names = [args.only] if args.only else list(SECTIONS)
    for name in names:
        fn = SECTIONS[name]
        kw = {}
        # Always forward --json (including None): without it, sections must
        # NOT write their module-default trajectory file as a side effect.
        if "json_out" in inspect.signature(fn).parameters:
            kw["json_out"] = args.json
        t0 = time.perf_counter()
        fn(**kw)
        print(f"[{name} done in {time.perf_counter() - t0:.1f}s]")


if __name__ == "__main__":
    main()
