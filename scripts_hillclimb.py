import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf hillclimb measurement harness: extrapolated per-device costs for a
(cfg overrides, hp) variant of one cell.

  PYTHONPATH=src python scripts_hillclimb.py zamba2-7b train_4k \
      remat=dots param_dtype=bfloat16 master=1
"""

import sys  # noqa: E402
import dataclasses  # noqa: E402

import jax  # noqa: E402

from repro import configs  # noqa: E402
from repro.distributed import sharding as shd  # noqa: E402
from repro.launch import mesh as mesh_lib  # noqa: E402
from repro.launch.dryrun import (_cell_costs, _depth_variants, _extrapolate,  # noqa: E402
                                 rules_for, serve_dtype)
from repro.models.config import SHAPES  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.roofline import analysis as R  # noqa: E402


def main() -> None:
    arch, shape_name = sys.argv[1], sys.argv[2]
    overrides, rule_over, master = {}, {}, False
    for kv in sys.argv[3:]:
        k, v = kv.split("=", 1)
        if k == "master":
            master = bool(int(v))
        elif k.startswith("rule."):
            rule_over[k[5:]] = (None if v == "none"
                                else tuple(v.split(",")))
        elif v in ("True", "False"):
            overrides[k] = v == "True"
        else:
            try:
                overrides[k] = int(v)
            except ValueError:
                overrides[k] = v
    shape = SHAPES[shape_name]
    cfg = serve_dtype(configs.get(arch, **overrides), shape)
    hp = adamw.Hparams(master_weights=master)
    mesh = mesh_lib.make_production_mesh()
    rules = dict(rules_for(shape, cfg), **rule_over)
    with shd.activate(mesh, rules):
        cfg1, cfg2, n_units = _depth_variants(cfg)
        total = _extrapolate(_cell_costs(cfg1, shape, hp),
                             _cell_costs(cfg2, shape, hp), n_units)
    coll = sum(total["coll"].values())
    t_c = total["flops"] / R.PEAK_FLOPS
    t_m = total["bytes"] / R.HBM_BW
    t_l = coll / R.LINK_BW
    mf = R.model_flops(cfg, shape)
    t_model = mf / 256 / R.PEAK_FLOPS
    bound = max(t_c, t_m, t_l)
    print(f"VARIANT {arch} {shape_name} {sys.argv[3:]}")
    print(f"  flops/dev={total['flops']:.3e} bytes/dev={total['bytes']:.3e} "
          f"coll/dev={coll:.3e}")
    print(f"  t_comp={t_c:.3f}s t_mem={t_m:.3f}s t_coll={t_l:.3f}s "
          f"useful={mf/(total['flops']*256):.2f} "
          f"roofline={100*t_model/bound:.1f}%")


if __name__ == "__main__":
    main()
