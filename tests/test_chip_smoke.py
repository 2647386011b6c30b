"""chip_smoke.py's phases at a tiny size on the CPU backend.

On the CPU the dispatcher resolves every stage to XLA and Pallas runs in
interpret mode, so these tests check the phases' control flow and checks,
not the chip: `main()` itself must refuse to run off a TPU.
"""

from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.launch import compile_cache
from repro.roofline import analysis as roofline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


smoke = _load_smoke()


@pytest.fixture(scope="module")
def runs():
    return smoke.fit_and_evaluate(4096, 3, 64, 1024, max_risk=0.05,
                                  expect_backend="xla")


def test_main_exits_nonzero_off_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_device_spec_raises_for_unknown_kind():
    assert roofline.device_spec("TPU v5 lite").source.startswith(
        "Google Cloud")
    assert roofline.device_spec("cpu").kind == "cpu"
    with pytest.raises(ValueError, match="no roofline spec"):
        roofline.device_spec("TPU v99 imaginary")


def test_fit_and_evaluate_phase(runs):
    assert set(runs) == {"auto", "fp32_tile", "xla"}
    for label, run in runs.items():
        assert set(run["plans"]) == {"kde", "solve", "predict"}
        assert run["scores"]["risk"] < 0.05, label
    pinned = runs["fp32_tile"]["plans"]
    assert pinned["solve"] == {"backend": "xla", "tile": 1024,
                               "source": "explicit", "precision": "fp32"}
    with pytest.raises(AssertionError, match="expected pallas"):
        smoke.fit_and_evaluate(2048, 3, 32, 1024, max_risk=0.05)


def test_kernel_parity_phase(runs):
    run = runs["auto"]
    err = smoke.kernel_parity(run["pipe"], run["data"].x, run["data"].y,
                              rows=2048, grid_size=32)
    assert set(err) == {"gram", "gram_rhs", "binned_scatter", "predict"}
    assert max(err.values()) < 1e-4


def test_serving_phase(runs):
    run = runs["auto"]
    out = smoke.serving(run["pipe"], run["data"].x, requests=24)
    assert out["requests"] == 24
    assert out["max_diff"] <= 1e-6


def test_compile_cache_location(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.configure() == os.path.join(REPO, ".jax_cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/cache")
        assert compile_cache.configure() == "/some/cache"
        assert jax.config.jax_compilation_cache_dir == "/some/cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_sharded_fit_phase_on_four_host_devices():
    """`--chips 4`'s phase on four forced host devices: rows split 4 ways,
    every O(n) artifact row-sharded, predictions within the stated
    tolerance of the one-device fit."""
    code = textwrap.dedent(f"""
        import importlib.util, sys
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {os.path.join(REPO, "chip_smoke.py")!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        out = smoke.sharded_fit(8192, 3, 64, chips=4, max_risk=0.05)
        assert sorted(out["shares"].values()) == [2048] * 4, out["shares"]
        print("SHARDED_OK", out["max_diff"])
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_OK" in out.stdout
