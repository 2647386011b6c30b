"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e.

The TPU compiler is installed with JAX, so each kernel is compiled here for
a described (not attached) ``v5e:2x2`` topology at the production widths:
what interpret mode on the CPU cannot show — Mosaic refusing a contraction
layout, an unaligned slice, too much VMEM — fails here instead of on the
chip.  Only one process may load the TPU library, so the topology is
described inside a module fixture (never at import) and every such test
lives in this one file.
"""

from __future__ import annotations

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.gram import ops as gram_ops
from repro.kernels.kde_binned import ops as kb_ops
from repro.kernels.pairwise import ops as pw_ops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 65_536        # one Gram / deposit row stream at production width
M = 1024             # landmarks at the standing size


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _custom_calls(lowered) -> int:
    return lowered.compile().as_text().count("tpu_custom_call")


@pytest.mark.parametrize("compensated", [False, True])
@pytest.mark.parametrize("precision", ["fp32", "bf16x3"])
def test_gram_kernel_compiles(one_chip, precision, compensated):
    """Fused Gram at n=65536, m=1024, d padded to 128, bm=bn=256 — fp32 is
    the mode every explicit-tile call pins."""
    acc = "compensated" if compensated else "plain"
    low = jax.jit(lambda x, y, w: gram_ops.gram(
        x, y, w, bm=256, bn=256, interpret=False, accumulator=acc,
        precision=precision)).lower(
            _spec((ROWS, 3), one_chip), _spec((M, 3), one_chip),
            _spec((ROWS,), one_chip))
    assert _custom_calls(low) >= 1


@pytest.fixture(scope="module")
def deposit_hlo(one_chip):
    """Compiled text of the Pallas deposit, one compile per shape: the
    global keyed sort takes most of a deposit's compile time."""
    cache = {}

    def compiled(d, grid, compensated, rows=ROWS):
        key = (d, grid, compensated, rows)
        if key not in cache:
            acc = "compensated" if compensated else "plain"
            cache[key] = jax.jit(lambda p, lo, sp: kb_ops.binned_scatter(
                p, lo, sp, grid, interpret=False, accumulator=acc)).lower(
                    _spec((rows, d), one_chip), _spec((d,), one_chip),
                    _spec((d,), one_chip)).compile().as_text()
        return cache[key]
    return compiled


@pytest.mark.parametrize("compensated", [False, True])
@pytest.mark.parametrize("d,grid", [(1, 1024), (2, 512), (3, 96)])
def test_deposit_kernel_compiles(deposit_hlo, d, grid, compensated):
    """CIC one-hot window deposit at the production grids (1024, 512^2,
    96^3), its (hi, lo) pair when compensated."""
    assert deposit_hlo(d, grid, compensated).count("tpu_custom_call") >= 1


def test_deposit_kernel_compiles_at_cell_rows(deposit_hlo):
    """The deposit at the benchmark cell's 1,000,000 rows on the 96^3
    lattice: 3,907 chunks of 256 points, their row bounds in SMEM."""
    hlo = deposit_hlo(3, 96, False, rows=1_000_000)
    assert hlo.count("tpu_custom_call") >= 1


@pytest.mark.parametrize("d,grid", [(1, 1024), (2, 512), (3, 96)])
def test_deposit_prep_sorts_once_without_gathers(deposit_hlo, d, grid):
    """At the production grids the deposit's point stream is ordered by
    one keyed sort that carries its payload: exactly one `sort` in the
    compiled module, and no gather applying the order afterwards."""
    hlo = deposit_hlo(d, grid, False)
    assert len(re.findall(r"\bsort\(", hlo)) == 1
    assert not re.findall(r"\bgather\(", hlo)


@pytest.mark.parametrize("d", [3, 8])   # exact per-coordinate / MXU distances
def test_pairwise_kernel_compiles(one_chip, d):
    low = jax.jit(lambda x, y: pw_ops.pairwise(
        x, y, nu=1.5, interpret=False)).lower(
            _spec((ROWS, d), one_chip), _spec((M, d), one_chip))
    assert _custom_calls(low) >= 1


def test_chip_smoke_compiled_kernels_phase(one_chip):
    """chip_smoke.py's compiled-kernels phase finds a tpu_custom_call in
    each of the three kernels at its own sizes."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    counts = smoke.compiled_kernels(sharding=one_chip)
    assert set(counts) == {"gram", "binned_scatter", "pairwise"}
    assert min(counts.values()) >= 1
