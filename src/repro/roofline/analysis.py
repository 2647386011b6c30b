"""Three-term roofline analysis from compiled dry-run artifacts.

All quantities are PER-DEVICE (the compiled module is the post-GSPMD
per-device program), so each term is directly a per-chip step-time lower
bound in seconds:

  compute    = device_flops / peak_flops          (197 TFLOP/s bf16, v5e)
  memory     = device_bytes_accessed / hbm_bw     (819 GB/s)
  collective = device_collective_bytes / link_bw  (~50 GB/s/link ICI)

device_flops / bytes come from compiled.cost_analysis(); collective bytes are
NOT in cost_analysis, so we parse the optimized HLO and sum operand sizes of
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute.  MODEL_FLOPS = 6·N·D (6·N_active·D for MoE) measures how
much of the compiled compute is "useful" (catches remat/redundancy waste).
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Optional

# TPU v5e published peaks per chip (Google Cloud documentation, "TPU v5e").
PEAK_FLOPS = 197e12         # bf16 FLOP/s per chip
HBM_BW = 819e9              # bytes/s per chip
LINK_BW = 50e9              # bytes/s per ICI link (1,600 Gbit/s over 4 links)


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """Per-device roofline constants the tile autotuner's analytic model
    feeds on (`repro.tuning.autotune`).

    The model only has to RANK a small pow2 tile ladder well enough that
    the measured top-k contains the true optimum; the micro-benchmark
    settles the final choice.  `step_overhead` is the fixed per-scan-step
    cost (dispatch + loop control + slab pad/reshape traffic) that punishes
    tiny tiles; `cache_bytes` is the working-set size past which a slab
    stops fitting the fast level of the memory hierarchy (VMEM on TPU,
    last-level cache on CPU) and the effective compute rate degrades.
    `matmul_costs` is the relative time per NOMINAL matmul flop under each
    Gram precision mode (`matmul_cost`).  `source` says where the numbers
    come from.
    """

    kind: str               # jax device_kind this row describes
    peak_flops: float       # FLOP/s the model ranks the fp32 Gram stream at
    mem_bw: float           # bytes/s to main memory
    step_overhead: float    # seconds of fixed cost per streamed tile
    cache_bytes: float      # fast-memory working-set budget
    matmul_costs: tuple     # ((precision, relative cost per flop), ...)
    source: str

    def matmul_cost(self, precision: str = "fp32") -> float:
        """Relative time per NOMINAL matmul flop under a precision mode.

        The split-precision Gram contraction (`repro.core.precision`)
        replaces one fp32 syrk by 3 (bf16x2) or 6 (bf16x3) bf16 partial
        matmuls.  Whether that wins depends on the device's bf16:f32
        matmul-rate ratio, so the autotuner's roofline scales the matmul
        share of its flop count by this factor: on the MXU (bf16 at 2x the
        f32 rate, plus fp32 inputs skipping the multi-pass f32 emulation)
        the split modes come out BELOW 1; on CPU the extra partial matmuls
        are a plain multiplier ABOVE 1 — which steers joint (tile,
        precision) resolution to fp32 there.
        """
        return dict(self.matmul_costs).get(precision, 1.0)


# Keyed by `jax.Device.device_kind`.  A kind missing here is an error
# (`device_spec`), never a silent default.
DEVICE_SPECS = {
    # v5e: peaks from Google Cloud's "TPU v5e" page; the fp32 Gram stream
    # is ranked at half the bf16 peak (multi-pass f32 on the MXU — an
    # assumption, not a measurement).  VMEM ~128 MB, a slab should leave
    # room for double buffering.  bf16x2's 3 partials ~0.375 and bf16x3's
    # 6 partials ~0.75 per nominal flop are modelled, not measured.
    "TPU v5 lite": DeviceSpec(
        "TPU v5 lite", PEAK_FLOPS / 2, HBM_BW, 5e-6, 64e6,
        (("fp32", 1.0), ("bf16x2", 0.375), ("bf16x3", 0.75)),
        source='Google Cloud "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM'),
    # The CPU backend the tests run on: a few AVX cores of GEMM, L2/L3-
    # bounded slabs, no bf16 units (each partial is an f32 GEMM plus split
    # overhead).  Coarse assumed constants, used only to rank tiles.
    "cpu": DeviceSpec(
        "cpu", 1e11, 3e10, 1e-4, 8e6,
        (("fp32", 1.0), ("bf16x2", 3.2), ("bf16x3", 6.4)),
        source="assumed constants for the XLA CPU backend"),
}


def device_spec(device_kind: str | None = None) -> DeviceSpec:
    """The `DEVICE_SPECS` row for a jax device kind (default: the first
    local device).  A kind that is not in the table raises: ranking plans
    for a chip with another chip's constants would hide the device."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_SPECS[device_kind]
    except KeyError:
        raise ValueError(
            f"no roofline spec for device kind {device_kind!r}; known kinds: "
            f"{sorted(DEVICE_SPECS)} (add a row with its published peaks to "
            f"repro.roofline.analysis.DEVICE_SPECS)") from None

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-collective-kind OPERAND bytes, summed over the module.

    Optimized HLO prints only the result type, so operand bytes are derived
    from it: all-gather concatenates group_size operands (operand = result /
    g); reduce-scatter consumes the pre-scatter operand (result * g);
    all-reduce / all-to-all / collective-permute are size-preserving.
    Tuple-shaped collectives contribute every element.
    """
    out: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    op_re = re.compile(
        r"\b(" + "|".join(_COLLECTIVES) + r")(-start|-done)?[.\d]*\(")
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if " = " not in stripped:
            continue
        _, rhs = stripped.split(" = ", 1)
        rhs = rhs.split("metadata=", 1)[0]  # op names recur in metadata
        m = op_re.search(rhs)
        if m is None:
            continue
        op, suffix = m.group(1), m.group(2)
        if suffix == "-done":
            continue  # counted at the matching -start
        # result type(s) = everything before the op token; tuple-typed
        # combined collectives reduce every element, so sum them all
        result_shapes = _SHAPE_RE.findall(rhs[:m.start()])
        if suffix == "-start" and len(result_shapes) >= 2:
            result_shapes = result_shapes[1:]  # (operand, results...)
        result = sum(_shape_bytes(d, s) for d, s in result_shapes)
        g = _group_size(stripped)
        if op == "all-gather":
            result = result // max(g, 1)
        elif op == "reduce-scatter":
            result = result * g
        out[op] += result
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    device_flops: float
    device_bytes: float
    device_collective_bytes: float
    collective_breakdown: dict
    model_flops_global: float
    memory_per_device: Optional[float] = None  # bytes, from memory_analysis

    @property
    def t_compute(self) -> float:
        return self.device_flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.device_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.device_collective_bytes / LINK_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        total = self.device_flops * self.chips
        return self.model_flops_global / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the compute roofline achieved if the step ran at the
        bound max(terms): useful_model_flops_time / achievable_step_time."""
        t_model = (self.model_flops_global / self.chips) / PEAK_FLOPS
        t_bound = max(self.t_compute, self.t_memory, self.t_collective)
        return t_model / t_bound if t_bound else 0.0

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d.update(t_compute=self.t_compute, t_memory=self.t_memory,
                 t_collective=self.t_collective, bottleneck=self.bottleneck,
                 useful_flops_ratio=self.useful_flops_ratio,
                 roofline_fraction=self.roofline_fraction)
        return d


def model_flops(cfg, shape) -> float:
    """6·N·D with N = active params (MoE) and D = tokens processed.

    decode shapes process global_batch tokens per step (one new token each).
    """
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.tokens  # forward only
    return 2.0 * n_active * shape.global_batch  # decode: 1 token per seq


def cost_dict(compiled) -> dict:
    """compiled.cost_analysis() as a flat {"flops": ..., "bytes accessed":
    ...} dict; empty where a backend reports nothing."""
    return compiled.cost_analysis() or {}


def achieved_throughput(cost: dict, seconds: float) -> dict:
    """Achieved GFLOP/s + bytes-moved columns from a `cost_dict` result.

    Divides the compiled program's counted flops / bytes by a MEASURED
    wall-clock, giving the attribution columns bench_pipeline records next
    to each stage's seconds: compute-bound stages show gflops_per_s near
    the device ceiling, bandwidth-bound ones show gbytes_per_s near the
    memory ceiling instead.  Zero/missing counters (backends without
    cost_analysis) degrade to zeros, never raise.
    """
    flops = float(cost.get("flops", 0.0) or 0.0)
    moved = float(cost.get("bytes accessed", 0.0) or 0.0)
    s = max(float(seconds), 1e-12)
    return {
        "gflops": flops / 1e9,
        "gflops_per_s": flops / 1e9 / s,
        "gbytes_moved": moved / 1e9,
        "gbytes_per_s": moved / 1e9 / s,
    }


def analyze(arch: str, shape, cfg, mesh_name: str, chips: int,
            compiled, hlo_text: str) -> Roofline:
    cost = cost_dict(compiled)
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(hlo_text)
    mem = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = float(getattr(ma, "temp_size_in_bytes", 0) +
                        getattr(ma, "argument_size_in_bytes", 0) +
                        getattr(ma, "output_size_in_bytes", 0))
    except Exception:
        pass
    return Roofline(
        arch=arch, shape=shape.name, mesh=mesh_name, chips=chips,
        device_flops=flops, device_bytes=bytes_accessed,
        device_collective_bytes=float(sum(coll.values())),
        collective_breakdown=coll,
        model_flops_global=model_flops(cfg, shape),
        memory_per_device=mem,
    )


def save(r: Roofline, path: str) -> None:
    with open(path, "w") as f:
        json.dump(r.to_dict(), f, indent=1)


def fmt_table(rows: list[dict]) -> str:
    hdr = (f"{'arch':<24}{'shape':<13}{'mesh':<7}{'t_comp(s)':>10}"
           f"{'t_mem(s)':>10}{'t_coll(s)':>10}{'bound':>11}"
           f"{'useful':>8}{'roofl%':>8}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r['arch']:<24}{r['shape']:<13}{r['mesh']:<7}"
            f"{r['t_compute']:>10.3e}{r['t_memory']:>10.3e}"
            f"{r['t_collective']:>10.3e}{r['bottleneck']:>11}"
            f"{r['useful_flops_ratio']:>8.2f}"
            f"{100*r['roofline_fraction']:>7.1f}%")
    return "\n".join(lines)
