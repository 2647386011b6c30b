"""The row-sharded fit on four forced host devices, at a small size.

A second `SAKRRPipeline.fit` of the same shapes under the same mesh reuses
every program the first one compiled, gives bitwise the same predictions,
and agrees with the plain reference (`bench.reference`).

The four-chip cell's limits on `pred_gap` and `risk_gap`
(`bench/limits/fig1_matern_x4.fit.json`) hold at the cell's 4e6 rows,
where the program reads about 2e-6.  At the 65,536 rows a CPU run can
afford, the solve amplifies float32 rounding a hundredfold on one device
and on four alike, so the predictions are held to the one-chip cell's
limits, as the benchmark's rehearsals hold every fit cell; the stages that
the sharding changes (the lattice psum, the normalised leverage, the
sharded top-k, the normal-equation psum) are held to the largest gap that
any sound run of the four-chip cell read on the chip.

Under the mesh the ``repro/kde`` and ``repro/solve`` spans carry the chip
count and the bytes each chip all-reduces, and the sharded KDE opens the
sub-spans the one-device path opens.
"""

import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = textwrap.dedent("""
    import json, sys
    sys.path[:0] = {paths!r}
    import jax
    import numpy as np
    from bench import compare, loops, reference
    from bench import run as bench_run
    from bench.tests import small

    compiles = []

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    files = small.cell("fig1_matern_x4.fit")
    bench_run.configure(files["config"], cache=False)
    spec = loops.deployment(files["config"], {seed})
    loop = loops.make(spec, files["traffic"], jax.devices()[:4])
    loop.make_data()

    def fit_and_predict():
        pipe = loop.fit_once()
        with loop.scope():
            pred = np.asarray(pipe.predict(loop.x))
        loop.last = pipe
        return pred

    first = fit_and_predict()
    before = len(compiles)
    second = fit_and_predict()
    in_second_fit = len(compiles) - before
    x, y, f_star = loop.reference_inputs()
    numbers = compare.fit_numbers(loop.outputs(), reference.fit(x, y, spec),
                                  x, f_star, spec)
    print(json.dumps({{
        "compiles_in_second_fit": in_second_fit,
        "predictions_equal": bool(np.array_equal(first, second)),
        "numbers": numbers}}))
""")


def _limits(workload: str) -> dict:
    with open(os.path.join(REPO, "bench", "limits", f"{workload}.json")) as f:
        return json.load(f)


def _run_child(seed: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(paths=[REPO, os.path.join(REPO, "src")], seed=seed)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_second_sharded_fit_compiles_nothing_and_matches_reference():
    out = _run_child(seed=2 ** 31 + 77)
    assert out["compiles_in_second_fit"] == 0, out
    assert out["predictions_equal"], out
    nums = out["numbers"]
    x4 = _limits("fig1_matern_x4.fit")
    assert sorted(x4["numbers"]) == ["pred_gap", "risk_gap"], x4["numbers"]
    for name, lim in _limits("fig1_matern.fit")["numbers"].items():
        assert nums[name] <= lim["limit"], (name, nums[name], lim)
    assert nums["landmarks_missed"] == 0, nums
    for name in ("density_gap", "probs_tv", "gram_gap"):
        assert nums[name] <= x4["not_compared"][name]["lower"], (name, nums)


SPANS = textwrap.dedent("""
    import glob, json, os, sys, tempfile
    sys.path[:0] = {paths!r}
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.data import krr_data
    from repro.distributed import sharding as shd
    from repro.launch import mesh as mesh_lib
    from repro.pipeline import PipelineConfig, SAKRRPipeline

    data = krr_data.bimodal(jax.random.PRNGKey(0), 16384, d=3)
    cfg = PipelineConfig(num_landmarks=32, kde_grid_size=32, tile=4096)
    mesh = mesh_lib.make_local_mesh("data", jax.devices()[:4])
    x = jax.device_put(data.x, NamedSharding(mesh, P("data", None)))
    y = jax.device_put(data.y, NamedSharding(mesh, P("data")))

    def fit(sharded):
        if not sharded:
            return SAKRRPipeline(cfg).fit(data.x, data.y)
        with shd.activate(mesh):
            return SAKRRPipeline(cfg).fit(x, y)

    out = {{}}
    for sharded in (False, True):
        fit(sharded)                 # compiles outside the trace
        path = tempfile.mkdtemp()
        with jax.profiler.trace(path):
            fit(sharded)
        xplane, = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True)
        events = [(ev.name, dict(ev.stats), ev.start_ns, ev.end_ns)
                  for plane in jax.profiler.ProfileData.from_file(
                      xplane).planes if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events
                  if ev.name.startswith("repro/")]
        out[str(sharded)] = events
    print(json.dumps(out))
""")


def test_sharded_stage_spans_carry_chips_and_psum_bytes():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SPANS.format(paths=[os.path.join(REPO, "src")])
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])

    def named(events, name):
        return [ev for ev in events if ev[0] == name]

    for name in ("repro/kde", "repro/solve"):
        (_, stats, _, _), = named(out["False"], name)
        assert "chips" not in stats and "psum_bytes" not in stats, stats
    (_, kde, k0, k1), = named(out["True"], "repro/kde")
    (_, solve, _, _), = named(out["True"], "repro/solve")
    assert (kde["chips"], kde["psum_bytes"]) == (4, 32 ** 3 * 4), kde
    assert (solve["chips"], solve["psum_bytes"]) == (4, (32 * 32 + 32) * 4)
    # the sharded KDE names its gaps as the one-device path does
    for sub in ("repro/kde/bandwidth", "repro/kde/deposit",
                "repro/kde/readback"):
        (_, _, s, e), = named(out["True"], sub)
        assert k0 <= s and e <= k1, sub


GRID = textwrap.dedent("""
    import json, sys
    sys.path[:0] = {paths!r}
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import kernels as K, nystrom
    from repro.data import krr_data
    from repro.distributed import sharding as shd
    from repro.launch import mesh as mesh_lib

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    data = krr_data.bimodal(jax.random.PRNGKey(0), 8192, d=3)
    kern = K.Matern(nu=1.5)
    idx = jax.numpy.arange(0, 8192, 128)
    lams = [1e-2, 1e-3, 1e-4, 1e-5]
    out = {{}}
    for name, mesh in (
            ("data", mesh_lib.make_local_mesh("data", jax.devices()[:4])),
            ("data_model", mesh_lib.make_local_mesh_2d(2))):
        x = jax.device_put(data.x, NamedSharding(mesh, P("data", None)))
        y = jax.device_put(data.y, NamedSharding(mesh, P("data")))
        with shd.activate(mesh):
            state = nystrom.normal_eq_state(kern, x, y, idx, tile=1024)
            nystrom.solve_from_state(state, lams).beta.block_until_ready()
            before = len(compiles)
            grid = nystrom.solve_from_state(state, lams)
            grid.beta.block_until_ready()
            out[name] = {{
                "compiles_in_second_solve": len(compiles) - before,
                "rows_equal_single": all(
                    np.array_equal(np.asarray(grid.beta[i]), np.asarray(
                        nystrom.solve_from_state(state, lam).beta))
                    for i, lam in enumerate(lams))}}
    print(json.dumps(out))
""")


def test_second_sharded_lam_grid_solve_compiles_nothing():
    """A lam grid solved again from the same sharded state, under a data
    mesh and a (data, model) mesh, compiles no program, and each row is
    bitwise the single-lam solve."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = GRID.format(paths=[os.path.join(REPO, "src")])
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for mesh in ("data", "data_model"):
        assert out[mesh]["compiles_in_second_solve"] == 0, out
        assert out[mesh]["rows_equal_single"], out
