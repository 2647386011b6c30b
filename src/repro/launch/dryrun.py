import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and record memory / cost / collective analysis.

The two lines above run before ANY other import (jax locks the device count
on first init); 512 placeholder host devices back the (2, 16, 16) multi-pod
mesh and the (16, 16) single-pod mesh.

Per cell we lower the REAL step function — train_step (fwd+bwd+AdamW) for
train shapes, prefill for prefill shapes, one-token decode_step with a full
KV/SSM cache for decode shapes — against pure ShapeDtypeStruct inputs (no
allocation), compile, and dump:

  * compiled.memory_analysis()   (fits-in-HBM evidence)
  * compiled.cost_analysis()     (FLOPs / bytes for the roofline)
  * collective bytes parsed from the optimized HLO

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import configs  # noqa: E402
from repro.distributed import sharding as shd  # noqa: E402
from repro.launch import mesh as mesh_lib  # noqa: E402
from repro.launch import specs  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.config import SHAPES, ModelConfig, ShapeSpec, supports_shape  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.roofline import analysis as roofline  # noqa: E402
from repro.training.train import make_train_step  # noqa: E402


def rules_for(shape: ShapeSpec, cfg: ModelConfig) -> dict:
    """Per-shape logical->mesh overrides (see DESIGN.md §5)."""
    if shape.kind == "train":
        return {"batch": ("pod", "data"), "seq_kv": None}
    if shape.kind == "prefill":
        # batch owns the data axis; the emitted KV caches shard their seq dim
        # over the (otherwise idle for caches) model axis — a 60-layer 32k
        # bf16 cache is ~16 GB/device if left replicated across 'model'
        return {"batch": ("pod", "data"), "seq_kv": ("model",)}
    if shape.name == "long_500k":
        # batch=1: DP is useless; shard the KV/state sequence dim instead (SP)
        return {"batch": None, "seq_kv": ("pod", "data")}
    # decode_32k: batch is plentiful (128); keep caches whole per replica
    return {"batch": ("pod", "data"), "seq_kv": None}


def step_and_args(cfg: ModelConfig, shape: ShapeSpec,
                  hp: adamw.Hparams | None = None):
    """(fn, abstract_args) for the cell's step function."""
    if shape.kind == "train":
        hp = hp or adamw.Hparams()
        fn = make_train_step(cfg, hp)
        params, opt = specs.train_state_spec(cfg, hp)
        batch = specs.batch_spec(cfg, shape)
        return fn, (params, opt, batch)
    if shape.kind == "prefill":
        def fn(params, inputs):
            return M.prefill(params, inputs, cfg)
        return fn, (specs.params_spec(cfg), specs.prefill_inputs_spec(cfg, shape))
    # decode
    def fn(params, tok, caches, index):
        return M.decode_step(params, tok, caches, index, cfg)
    tok, index = specs.decode_inputs_spec(cfg, shape)
    return fn, (specs.params_spec(cfg), tok, specs.caches_spec(cfg, shape),
                index)


def serve_dtype(cfg: ModelConfig, shape: ShapeSpec) -> ModelConfig:
    """Serving cells hold bf16 weights (deployment numerics)."""
    if shape.kind == "train":
        return cfg
    return dataclasses.replace(cfg, param_dtype="bfloat16")


def _donate(shape: ShapeSpec) -> tuple[int, ...]:
    if shape.kind == "train":
        return (0, 1)        # params, opt_state
    if shape.kind == "decode":
        return (2,)          # caches
    return ()


def _cell_costs(cfg: ModelConfig, shape: ShapeSpec,
                hp: adamw.Hparams | None = None) -> dict:
    """flops / bytes / collective-bytes of one compiled variant (per device)."""
    fn, args = step_and_args(cfg, shape, hp)
    compiled = jax.jit(fn).lower(*args).compile()
    cost = roofline.cost_dict(compiled)
    coll = roofline.collective_bytes(compiled.as_text() or "")
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": {k: float(v) for k, v in coll.items()},
    }


def _depth_variants(cfg: ModelConfig) -> tuple[ModelConfig, ModelConfig, int]:
    """(depth-1 cfg, depth-2 cfg, n_units) for exact-cost extrapolation.

    XLA's cost analysis counts a while-loop body once, so the scanned
    production program under-reports layer costs.  We compile the SAME cell
    unrolled at depths d1 < d2 (cheap) and extrapolate linearly: with
    unit = c(d2) - c(d1) and base = c(d1) - unit, total = base + n_units*unit.
    For hybrids the repeating unit is a whole group (mamba x per + shared
    attn); the tail layers are present in both depths, i.e. in `base`.

    The cost variants also switch attention to the dense oracle ("xla"):
    the production xla_chunked path hides the q-chunk loop inside another
    while-loop that cost analysis would count once, while the dense oracle
    computes the IDENTICAL flops/bytes with no inner loop.  (Compile-only:
    the s x s logits buffer is never allocated.)
    """
    if cfg.family == "hybrid":
        _, _, tail = M.hybrid_counts(cfg)
        d1 = cfg.attn_every + tail
        d2 = 2 * cfg.attn_every + tail
        n_units = cfg.num_layers // cfg.attn_every
    else:
        d1, d2, n_units = 1, 2, cfg.num_layers
    mk = lambda d: dataclasses.replace(cfg, num_layers=d, scan_layers=False,
                                       attention_impl="xla")
    return mk(d1), mk(d2), n_units


def _extrapolate(c1: dict, c2: dict, n_units: int) -> dict:
    def lin(a, b):
        unit = b - a
        return (a - unit) + n_units * unit
    coll = {k: lin(c1["coll"][k], c2["coll"][k]) for k in c1["coll"]}
    return {"flops": lin(c1["flops"], c2["flops"]),
            "bytes": lin(c1["bytes"], c2["bytes"]), "coll": coll}


SP_PREFILL_RULES = {
    # §Perf cell B: sequence-parallel prefill — activations/logits shard the
    # seq dim over 'model'; heads stay replicated (no uneven-head padding),
    # K/V get all-gathered per layer (the only collective).  2.1x roofline
    # on llava-next-34b prefill_32k; the win generalises to every
    # full-attention prefill cell.
    "seq": ("model",), "heads": None, "kv_heads": None, "mlp": None,
}


def run_cell(arch: str, shape_name: str, mesh_name: str, out_dir: str | None,
             verbose: bool = True, exact_costs: bool = True,
             sp_prefill: bool = False) -> dict:
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    ok, reason = supports_shape(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": reason}
    cfg = serve_dtype(cfg, shape)
    mesh = mesh_lib.make_production_mesh(
        multi_pod=(mesh_name == "multi"),
        num_devices=512 if mesh_name == "multi" else 256)
    chips = mesh.devices.size
    rules = rules_for(shape, cfg)
    tag = ""
    if sp_prefill and shape.kind == "prefill":
        rules = dict(rules, **SP_PREFILL_RULES)
        tag = "+sp"
    t0 = time.perf_counter()
    with shd.activate(mesh, rules):
        # 1) production (scanned) program: THE dry-run compile + memory proof
        fn, args = step_and_args(cfg, shape)
        lowered = jax.jit(fn, donate_argnums=_donate(shape)).lower(*args)
        compiled = lowered.compile()
        r = roofline.analyze(arch, shape, cfg, mesh_name, chips, compiled,
                             compiled.as_text() or "")
        scanned = {"flops": r.device_flops, "bytes": r.device_bytes,
                   "coll": dict(r.collective_breakdown)}
        try:
            mem_str = str(compiled.memory_analysis())
        except Exception as e:  # pragma: no cover
            mem_str = f"<memory_analysis unavailable: {e}>"
        # 2) exact per-layer costs from unrolled depth-1/2 compiles
        if exact_costs:
            cfg1, cfg2, n_units = _depth_variants(cfg)
            total = _extrapolate(_cell_costs(cfg1, shape),
                                 _cell_costs(cfg2, shape), n_units)
            r.device_flops = total["flops"]
            r.device_bytes = total["bytes"]
            r.collective_breakdown = total["coll"]
            r.device_collective_bytes = float(sum(total["coll"].values()))
    dt = time.perf_counter() - t0
    r.arch = arch + tag
    rec = r.to_dict()
    rec.update(status="ok", compile_seconds=dt, memory_analysis=mem_str,
               scanned_costs=scanned)
    if verbose:
        print(f"[{mesh_name}] {arch}{tag} x {shape_name}: OK in {dt:.1f}s | "
              f"flops/dev={r.device_flops:.3e} bytes/dev={r.device_bytes:.3e} "
              f"coll/dev={r.device_collective_bytes:.3e} "
              f"bound={r.bottleneck} useful={r.useful_flops_ratio:.2f} "
              f"roofline={100*r.roofline_fraction:.1f}%")
        print(f"  memory_analysis: {mem_str[:300]}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir,
                            f"{mesh_name}__{arch}{tag}__{shape_name}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(configs.ARCHS))
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--sp-prefill", action="store_true",
                    help="sequence-parallel prefill rules (§Perf optimized)")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cell_list = [(a, s) for a in configs.ARCHS for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cell_list = [(args.arch, args.shape)]

    failures = []
    for mesh_name in meshes:
        for arch, shape_name in cell_list:
            try:
                rec = run_cell(arch, shape_name, mesh_name, args.out,
                               sp_prefill=args.sp_prefill)
                if rec["status"] == "skipped":
                    print(f"[{mesh_name}] {arch} x {shape_name}: SKIP "
                          f"({rec['reason']})")
            except Exception:
                failures.append((mesh_name, arch, shape_name))
                print(f"[{mesh_name}] {arch} x {shape_name}: FAILED")
                traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run cells failed: {failures}")
    print("dry-run complete: all requested cells compiled.")


if __name__ == "__main__":
    main()
