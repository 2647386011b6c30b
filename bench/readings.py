"""Readings that a cell's limits are set from, taken in one process.

  python3 -m bench.readings --workload <name> --seeds 1,2,... \
      --control-seeds 101,102,103 [--seconds 2] [--out <file.jsonl>]

For each of ``--seeds`` the program runs the cell's loop (set-up and a
short window at the cell's own size and load) and the comparison reads its
gaps to the reference: the lower readings.  For each of ``--control-seeds``
the reference computed in the configuration's ``control_precision`` takes
the program's place: the upper readings.  One JSON line per seed goes to
stdout and to ``--out``.  Like the benchmark itself it refuses to run off a
TPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

from bench import run as bench_run


def _numbers(kind: str, loop, spec: dict, produced: dict) -> dict:
    from bench import compare, reference

    if kind == "fit":
        x, y, f_star = loop.reference_inputs()
        ref = reference.fit(x, y, spec)
        return compare.fit_numbers(produced, ref, x, f_star, spec)
    return compare.serve_numbers(produced["answers"], produced["rows"],
                                 loop.model, spec)


def program_reading(files: dict, devices, seed: int, seconds: float) -> dict:
    from bench import loops

    spec = loops.deployment(files["config"], seed)
    loop = loops.make(spec, files["traffic"], devices)
    loop.setup(seconds)
    result = loop.window(seconds)
    produced = loop.outputs()
    numbers = _numbers(files["traffic"]["loop"], loop, spec, produced)
    return {"side": "program", "seed": seed, "numbers": numbers,
            "metrics": result["metrics"]}


def control_reading(files: dict, devices, seed: int, seconds: float) -> dict:
    """The reference in the program's place, computed in the precision just
    below the one the configuration states (its ``control_precision``), on
    the same inputs: a whole fit, or, for a serving cell, the served
    model's predictions on the same checked requests."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import loops, reference

    mode = files["config"]["control_precision"]
    spec = loops.deployment(files["config"], seed)
    loop = loops.make(spec, files["traffic"], devices)
    loop.make_data()
    x, y, _ = loop.reference_inputs()
    if files["traffic"]["loop"] == "fit":
        low = reference.fit(x, y, spec, mode=mode)
        produced = {k: np.asarray(jax.device_get(v)) for k, v in low.items()}
    else:
        # the served model, predicted in the control's precision
        loop.model = reference.fit(x, y, spec)
        loop.load_pool()
        loop.prepare(seconds, float(files["traffic"]["rate_per_s"]))
        rows = [loop.request_rows(i) for i in loop.checked()]
        items = reference.kern_items(spec["kernel"])
        answers = [np.asarray(reference.predict(
            jnp.asarray(r), loop.model["landmarks"], loop.model["beta"],
            kern_items=items, mode=mode)) for r in rows]
        produced = {"rows": rows, "answers": answers}
    numbers = _numbers(files["traffic"]["loop"], loop, spec, produced)
    return {"side": "control", "seed": seed, "numbers": numbers}


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(bench_run.ROOT, "src"))
    files = bench_run.load_cell(args.workload)
    devices, _, _ = bench_run.device_check(int(files["cell"]["chips"]))
    bench_run.configure(files["config"])
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    jobs = ([(program_reading, s) for s in seeds]
            + [(control_reading, s) for s in controls])
    for fn, seed in jobs:
        t0 = time.perf_counter()
        rec = fn(files, devices, seed, args.seconds)
        rec["workload"] = args.workload
        rec["wall_s"] = time.perf_counter() - t0
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
        gc.collect()


if __name__ == "__main__":
    main()
