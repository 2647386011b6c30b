"""Distributed-runtime tests on forced host devices (subprocess isolation).

jax locks the device count at first backend init, so every case that needs
multiple devices runs in a fresh subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8.  Covers: logical-rule
spec mapping (pure unit tests), sharded train step numerics vs single-device,
dry-run cell lowering on a reduced mesh, and roofline HLO parsing.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

from repro.roofline import analysis as roofline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str) -> str:
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import jax.numpy as jnp
    """) + textwrap.dedent(body)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS="")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ------------------------------------------------------------ rule mapping --
def test_logical_rules_dedupe_and_divisibility():
    import jax
    from repro.distributed import sharding as shd
    mesh = jax.make_mesh((1,), ("data",))

    class FakeMesh:  # shape-only stand-in for spec computation
        axis_names = ("pod", "data", "model")
        class devices:
            shape = (2, 16, 16)

    act = shd.Activation(FakeMesh, dict(shd.DEFAULT_RULES))
    # batch takes (pod, data); seq_kv would reuse data -> dropped
    spec = act.spec(("batch", "kv_heads", "seq_kv", None))
    assert spec[0] == ("pod", "data") and spec[1] == "model"
    assert spec[2] is None and spec[3] is None
    # non-divisible dims lose mesh axes (50280 % 16 != 0)
    spec = act.spec(("vocab", "embed"), shape=(50280, 768))
    assert spec[0] is None and spec[1] == "data"
    # divisible dims keep them
    spec = act.spec(("vocab", "embed"), shape=(50304, 768))
    assert spec[0] == "model"


# ------------------------------------------------- sharded == single device --
@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    out = run_sub("""
        import numpy as np
        from repro import configs
        from repro.data import tokens
        from repro.distributed import sharding as shd
        from repro.launch import mesh as mesh_lib
        from repro.models import model as M
        from repro.optim import adamw
        from repro.training.train import make_train_step
        import dataclasses

        cfg = dataclasses.replace(configs.get_smoke("llama3.2-1b"),
                                  compute_dtype="float32")
        hp = adamw.Hparams(clip_norm=1e9)
        data = tokens.for_config(cfg, batch=8, seq_len=16)
        batch = data.batch_at(0)
        params = M.init(jax.random.PRNGKey(0), cfg)
        opt = adamw.init(params)
        step = make_train_step(cfg, hp)

        # single-device reference
        p_ref, _, m_ref = jax.jit(step)(params, opt, batch)

        # 2x4 mesh (data x model), sharded params + batch
        mesh = mesh_lib.make_local_mesh_2d(model_parallelism=4)
        with shd.activate(mesh):
            p_sh, _, m_sh = jax.jit(step)(params, opt, batch)
        np.testing.assert_allclose(float(m_ref["loss"]),
                                   float(m_sh["loss"]), rtol=2e-4)
        for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)
        print("SHARDED_MATCH_OK")
    """)
    assert "SHARDED_MATCH_OK" in out


@pytest.mark.slow
def test_dryrun_cell_compiles_on_reduced_mesh():
    out = run_sub("""
        import dataclasses
        from repro import configs
        from repro.distributed import sharding as shd
        from repro.launch import mesh as mesh_lib, specs
        from repro.launch.dryrun import rules_for, step_and_args
        from repro.models.config import SHAPES
        from repro.roofline import analysis as roofline

        # reduced-size mixtral on a 2x4 mesh with a scaled-down train shape
        cfg = configs.get_smoke("mixtral-8x7b")
        shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64,
                                    global_batch=8)
        mesh = mesh_lib.make_local_mesh_2d(model_parallelism=4)
        with shd.activate(mesh, rules_for(shape, cfg)):
            fn, args = step_and_args(cfg, shape)
            compiled = jax.jit(fn).lower(*args).compile()
            cost = roofline.cost_dict(compiled)
            assert cost.get("flops", 0) > 0
        print("CELL_COMPILE_OK", int(cost["flops"]))
    """)
    assert "CELL_COMPILE_OK" in out


# ----------------------------------------------------------- HLO parsing ----
def test_collective_bytes_parser():
    hlo = """
  %all-gather.12 = f32[512,2048]{0,1} all-gather(%x), channel_id=1, replica_groups=[16,16]<=[256], dimensions={1}
  %all-reduce.3 = bf16[128,64]{1,0} all-reduce(%y), replica_groups={{0,1,2,3}}, to_apply=%add
  %reduce-scatter.7 = f32[32,16]{1,0} reduce-scatter(%z), replica_groups=[8,2]<=[16], dimensions={0}
  %add.1 = f32[4,4]{1,0} add(%a, %b)
  %collective-permute.9 = f32[10]{0} collective-permute(%w), source_target_pairs={{0,1}}
"""
    out = roofline.collective_bytes(hlo)
    assert out["all-gather"] == 512 * 2048 * 4 // 16
    assert out["all-reduce"] == 128 * 64 * 2
    assert out["reduce-scatter"] == 32 * 16 * 4 * 2
    assert out["collective-permute"] == 10 * 4
    assert out["all-to-all"] == 0


def test_collective_bytes_parser_tuple_async_metadata():
    # tuple-typed combined all-reduce (XLA all-reduce combiner), async
    # start/done pairs, and op names recurring inside metadata strings
    hlo = """
  %all-reduce.5 = (f32[1280,1280]{0,1}, f32[1280]{0}) all-reduce(%a, %b), channel_id=4, replica_groups=[1,256]<=[256], to_apply=%add
  %all-gather-start.2 = (f32[64,8]{1,0}, f32[64,128]{1,0}) all-gather-start(%x), channel_id=9, replica_groups=[4,16]<=[64], dimensions={1}
  %all-gather-done.2 = f32[64,128]{1,0} all-gather-done(%all-gather-start.2)
  %fusion.77 = f32[256,256]{1,0} fusion(%c), kind=kLoop, metadata={op_name="jit(f)/all-reduce/fake"}
"""
    out = roofline.collective_bytes(hlo)
    assert out["all-reduce"] == (1280 * 1280 + 1280) * 4
    assert out["all-gather"] == 64 * 128 * 4 // 16  # result/group from -start
    # the fusion line's metadata mention must NOT count
    assert sum(out.values()) == out["all-reduce"] + out["all-gather"]


def test_roofline_terms_and_bottleneck():
    r = roofline.Roofline(
        arch="a", shape="train_4k", mesh="single", chips=256,
        device_flops=1.97e14, device_bytes=819e9 * 2.0,
        device_collective_bytes=50e9 * 0.5, collective_breakdown={},
        model_flops_global=1.97e14 * 256 * 0.8)
    assert abs(r.t_compute - 1.0) < 1e-9
    assert abs(r.t_memory - 2.0) < 1e-9
    assert abs(r.t_collective - 0.5) < 1e-9
    assert r.bottleneck == "memory"
    assert abs(r.useful_flops_ratio - 0.8) < 1e-9
    assert abs(r.roofline_fraction - 0.4) < 1e-9
