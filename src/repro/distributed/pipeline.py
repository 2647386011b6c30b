"""GPipe-style pipeline parallelism (PP) building block.

``gpipe`` runs S pipeline stages (layer stacks) sharded over a mesh axis,
streaming M microbatches with the classic (M + S - 1)-tick schedule:
stage k processes microbatch (t - k) at tick t, activations hop stage->stage
via collective_permute, and the last stage emits results.  Bubble fraction
is the usual (S-1)/(M+S-1).

Scope note (DESIGN.md §5): the assigned configs fit 256-512 chips with
DP/FSDP/TP/SP/EP, so the production launchers do not enable PP; this module
is the validated building block for the >100B-dense regime where a 'stage'
mesh axis becomes necessary.  Equivalence vs sequential execution is tested
on forced host devices (tests/test_pipeline_pp.py).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def gpipe(layer_fn: Callable, stage_params, x: Array, *, mesh: Mesh,
          axis: str = "stage") -> Array:
    """Apply S stages to M microbatches with pipelined execution.

    layer_fn(params_for_one_stage, h) -> h', where h is one microbatch.
    stage_params: pytree whose leaves have leading dim S (stage-stacked).
    x: (M, ...) microbatches, replicated.
    Returns (M, ...) = sequential application of all stages (tested).
    """
    S = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    M = x.shape[0]
    fwd = [(i, i + 1) for i in range(S - 1)]  # stage i -> i+1

    def _varying(v):  # mark as device-varying for the scan carry typing
        return jax.lax.pcast(v, (axis,), to="varying")

    def body(local_params, xs):
        lp = jax.tree.map(lambda a: a[0], local_params)  # this stage's params
        idx = jax.lax.axis_index(axis)
        buf = _varying(jnp.zeros(xs.shape[1:], xs.dtype))
        outs = _varying(jnp.zeros_like(xs))

        def tick(carry, t):
            buf, outs = carry
            inject = xs[jnp.clip(t, 0, M - 1)]
            h_in = jnp.where(idx == 0, inject, buf)
            h_out = layer_fn(lp, h_in)
            buf_next = jax.lax.ppermute(h_out, axis, fwd)
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            emit = jnp.logical_and(t >= S - 1, idx == S - 1)
            outs = jnp.where(emit, outs.at[out_idx].set(h_out), outs)
            return (buf_next, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs),
                                      jnp.arange(M + S - 1))
        # replicate the last stage's collected outputs to every stage
        outs = jax.lax.psum(
            jnp.where(idx == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stage_params), P()),
        out_specs=P(),
    )(stage_params, x)


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    return (num_stages - 1) / (num_microbatches + num_stages - 1)
