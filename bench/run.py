"""Run one cell of the benchmark once and print its result line.

  python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its limits are found by
name from BENCHMARK.json: ``bench/configs/<config>.json``,
``bench/traffic/<mix>.json``, ``bench/limits/<workload>.json``, and one
reader per per-layer metric in ``bench/layer_metrics/<metric>.py``.

Set-up (inputs made on the device from the seed, one warm-up of every shape
the window uses) is timed from process start; then the window runs for
``--seconds``; then the comparison with the plain reference decides
``correct``.  With ``--trace 0`` the result holds the cell's end-to-end
metrics; with ``--trace 1`` a profiler trace of the window gives its
per-layer metrics.  Without a TPU, or with fewer chips than the cell asks
for, or on a chip missing from ``bench/peaks.json``, it exits non-zero and
prints no result.  The last line of stdout is one JSON object.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


def _process_age_s() -> float:
    """Seconds since this process started (Linux /proc), so that set-up
    includes interpreter start and imports."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


T_START = time.perf_counter() - _process_age_s()


class Refused(SystemExit):
    """No run: the device or the cell is not what the benchmark needs."""


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload: str, manifest: str = MANIFEST) -> dict:
    """Everything a manifest (BENCHMARK.json's format) and the files it
    names say about a cell."""
    manifest = _load_json(manifest)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise Refused(f"bench: no workload {workload!r} in the manifest")
    cell = cells[workload]
    conf = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    limits_file = os.path.join(BENCH, "limits", f"{workload}.json")
    return {
        "cell": cell,
        "config": _load_json(ROOT, conf["file"]),
        "traffic": _load_json(BENCH, "traffic", f"{cell['traffic']}.json"),
        "limits": (_load_json(limits_file)["numbers"]
                   if os.path.exists(limits_file) else {}),
        "end_to_end": [m for m in manifest["end_to_end"]
                       if workload in m.get("workloads", [workload])],
        "per_layer": [m for m in manifest["per_layer"]
                      if workload in m.get("workloads", [workload])],
    }


def device_check(chips: int, *, require_tpu: bool = True) -> tuple:
    """(devices to use, device record, peak entry); refuses a run that is
    not on enough TPU chips of a kind that peaks.json knows."""
    import jax

    devices = jax.devices()
    kind = devices[0].device_kind
    record = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices)}
    _say(f"device: platform={record['platform']} kind={kind} "
         f"count={record['count']}")
    peaks = _load_json(BENCH, "peaks.json")
    if require_tpu:
        if record["platform"] != "tpu":
            raise Refused(f"bench: needs a TPU; JAX found "
                          f"{record['platform']!r}")
        if len(devices) < chips:
            raise Refused(f"bench: the cell needs {chips} chip(s); JAX "
                          f"found {len(devices)}")
        if kind not in peaks:
            raise Refused(f"bench: no peaks for device kind {kind!r} in "
                          f"bench/peaks.json")
    if len(devices) < chips:
        raise Refused(f"bench: the cell needs {chips} device(s)")
    return devices[:chips], record, peaks.get(kind)


def configure(config: dict, *, cache: bool = True) -> None:
    """The matrix-product precision the configuration states, for every
    thread of the process; and JAX's persistent compilation cache at a
    fixed path in the checkout, whatever the environment says, with every
    compile kept in it."""
    import jax

    jax.config.update("jax_default_matmul_precision",
                      config["matmul_precision"])
    if cache:
        # JAX writes its entries into the directory but does not make it
        os.makedirs(CACHE_DIR, exist_ok=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
        from repro.launch import compile_cache
        compile_cache.configure()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def _reader(name: str):
    path = os.path.join(BENCH, "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench.layer_metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def layer_metrics(metrics: list, record: dict) -> dict:
    out = {}
    for m in metrics:
        value = _reader(m["name"])(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def _memory_peak(devices) -> int | None:
    peaks = []
    for dev in devices:
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _plain(v):
    return v if isinstance(v, (int, float)) and math.isfinite(v) else str(v)


def run(files: dict, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True) -> dict:
    """One run of the cell that ``files`` (from `load_cell`) describes;
    returns the result object.  ``require_tpu=False`` is a rehearsal: it
    runs on whatever JAX finds, with no persistent compilation cache."""
    from bench import compare, loops, reference
    from bench import trace_reduce
    from bench.compile_clock import CompileClock

    conf, mix, limits = files["config"], files["traffic"], files["limits"]
    devices, dev_record, peak = device_check(int(files["cell"]["chips"]),
                                             require_tpu=require_tpu)
    configure(conf, cache=require_tpu)
    import jax

    clock = CompileClock()
    spec = loops.deployment(conf, seed)
    loop = loops.make(spec, mix, devices)
    with tempfile.TemporaryDirectory(prefix="bench_plans_") as plans:
        # plans are resolved afresh in every run, never read from another
        os.environ["REPRO_TUNE_CACHE"] = os.path.join(plans, "autotune.json")
        loop.setup(seconds)
        trace_dir = None
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        at_window = clock.snapshot()
        setup_s = time.perf_counter() - T_START
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            result = loop.window(seconds)
        in_window = {k: v - at_window[k] for k, v in clock.snapshot().items()}
        if trace:
            jax.profiler.stop_trace()
    _say(f"set-up: {setup_s:.3f}s; compiles before the window "
         f"{at_window}; in the window {in_window}")
    dev_record["memory_peak_bytes"] = _memory_peak(devices)

    out = {"correct": False, "attempted": result["attempted"],
           "failed": result["failed"]}
    if trace:
        path = trace_reduce.find_file(trace_dir)
        tr = trace_reduce.load(path, host_as_device=not require_tpu)
        shutil.rmtree(trace_dir, ignore_errors=True)
        record = {"window": result, "trace": tr, "spec": spec,
                  "peak": peak, "chips": len(devices)}
        out["metrics"] = layer_metrics(files["per_layer"], record)
        dev_record["busy_s"] = trace_reduce.mean_busy_s(tr)
        dev_record["window_s"] = tr.window_s
        out["breakdown"] = {"device_ops": trace_reduce.top_ops(tr),
                            "idle_gaps": trace_reduce.idle_gaps(tr)}
    else:
        e2e = dict(result["metrics"], setup_s=setup_s)
        out["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
                          for m in files["end_to_end"] if m["name"] in e2e}
    out["device"] = dev_record

    produced = loop.outputs()
    gc.collect()
    t_ref = time.perf_counter()
    if mix["loop"] == "fit":
        x, y, f_star = loop.reference_inputs()
        del loop
        ref = reference.fit(x, y, spec)
        numbers = compare.fit_numbers(produced, ref, x, f_star, spec)
    else:
        numbers = compare.serve_numbers(produced["answers"], produced["rows"],
                                        loop.model, spec)
    _say(f"reference and comparison: {time.perf_counter() - t_ref:.3f}s")
    out["correct"], checked = compare.judge(numbers, limits)
    out["numbers"] = {k: _plain(v) for k, v in numbers.items()}
    out["check"] = {k: {"value": _plain(v["value"]), "limit": v["limit"]}
                    for k, v in checked.items()}
    for name, c in out["check"].items():
        _say(f"check {name}: {c['value']} (limit {c['limit']})")
    _say(f"correct: {out['correct']}")
    return out


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    out = run(load_cell(args.workload), args.seed, args.seconds,
              bool(args.trace))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
