"""Each cell's loop end to end at a small size on the CPU: set-up, window,
the result line's metrics, the trace readers and the comparison.  The
four-chip cell runs on four forced host devices in a child process."""

import json
import math
import os
import subprocess
import sys

import pytest

from bench import run as bench_run
from bench.tests import small

SEED = 2 ** 31 + 12345
ONE_CHIP = ["fig1_matern.fit", "fig1_matern.serve_poisson",
            "fig3_gaussian.fit"]


def rehearse(workload, trace=False, seed=SEED):
    return bench_run.run(small.cell(workload), seed, 1.5, trace,
                         require_tpu=False)


def _names(kind, workload):
    return {m["name"] for m in small.cell(workload)[kind]}


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_cell_runs_and_reports_its_metrics(workload):
    out = rehearse(workload)
    assert set(out["metrics"]) == _names("end_to_end", workload)
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] >= 1
    assert list(out)[-1] == "check"
    assert all(math.isfinite(float(v)) for v in out["numbers"].values())


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_traced_cell_reads_its_layers(workload):
    out = rehearse(workload, trace=True)
    names = _names("per_layer", workload)
    # the Gram kernel's roofline needs the TPU kernel's events
    expected = names - {"gram_roofline_pct"}
    assert expected <= set(out["metrics"]) <= names
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["breakdown"]["device_ops"]


X4 = """
import json, sys
sys.path[:0] = {paths!r}
from bench import run as bench_run
from bench.tests import small
out = bench_run.run(small.cell("fig1_matern_x4.fit"), {seed}, 1.5, False,
                    require_tpu=False)
print(json.dumps(out))
"""


def rehearse_x4(prelude=""):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = prelude + X4.format(paths=[bench_run.ROOT,
                                      os.path.join(bench_run.ROOT, "src")],
                               seed=SEED)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_four_chip_cell_runs_on_forced_host_devices():
    out = rehearse_x4()
    assert set(out["metrics"]) == _names("end_to_end", "fig1_matern_x4.fit")
    assert out["attempted"] > 0
    assert all(math.isfinite(float(v)) for v in out["numbers"].values())
