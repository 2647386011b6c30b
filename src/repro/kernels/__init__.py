"""Pallas TPU kernels for the perf-critical hot spots.

Each subpackage follows the kernel.py (pl.pallas_call + BlockSpec) /
ops.py (jit'd public wrapper) / ref.py (pure-jnp oracle) structure and is
validated in interpret mode on CPU (tests/test_pallas_*.py).

  pairwise        — tiled stationary-kernel (Gram) matrix      [paper hot spot]
  gram            — fused kernel-tile + K_nm^T K_nm accumulate [streaming solve]
  kde             — tiled direct Gaussian KDE                  [paper hot spot]
  kde_binned      — tiled CIC scatter, VMEM-resident grid      [binned KDE deposit]
  flash_attention — causal GQA flash attention (+ SWA)         [LM prefill]
  ssd             — Mamba2 SSD chunked scan                    [SSM mixing]

`repro.kernels.dispatch` picks Pallas vs fused-XLA per call site ('auto' =
Pallas on TPU, XLA elsewhere).
"""

from __future__ import annotations

import jax


def out_vma(*arrays) -> frozenset:
    """The mesh axes a kernel's outputs vary over: the union of its
    inputs'.  Inside a `jax.shard_map` body (`check_vma=True`) a
    `pl.pallas_call` must declare it on every output ShapeDtypeStruct;
    outside one it is empty."""
    return frozenset().union(*(jax.typeof(a).vma for a in arrays))


def resolve_interpret(interpret: bool | None) -> bool:
    """Pallas execution mode for ``interpret=None``.

    Compiled (Mosaic) on TPU; the Pallas interpreter on the CPU backend,
    where the tests validate the kernels.  Any other platform has neither
    and raises, so a kernel never silently runs interpreted on a chip.
    """
    if interpret is not None:
        return bool(interpret)
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for TPU and are interpreted on CPU; "
        f"backend {platform!r} has neither — use backend='xla'")
