"""The benchmark command refuses to run, and prints no result, off a TPU
and in a checkout that holds only the benchmark's own files."""

import os
import shutil
import subprocess
import sys

from bench import run as bench_run

ARGS = ["-m", "bench.run", "--workload", "fig1_matern.fit", "--seed",
        "2147483999", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable] + ARGS, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    proc = _run(bench_run.ROOT)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(bench_run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(bench_run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
