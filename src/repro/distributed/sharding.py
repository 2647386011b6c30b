"""Logical-axis sharding rules (MaxText-style) for the production meshes.

Model code never names mesh axes directly; it annotates arrays with *logical*
axes ("batch", "embed", "mlp", ...) via ``constrain``.  A ShardingRules table
maps logical axes to mesh axes (or None = replicated).  ``activate(mesh,
rules)`` installs the mapping and sets the mesh as the jit mesh
(``jax.set_mesh``); with no active mapping every annotation is a no-op, so
the same model code runs on a laptop CPU and on a 512-chip mesh.  Meshes
carry ``AxisType.Auto`` axes (`repro.launch.mesh`): logical constraints are
``with_sharding_constraint`` hints, which Explicit axes refuse.

Default rules (single-pod (data=16, model=16); multi-pod adds a leading
"pod" axis used for batch + an extra FSDP shard of the weights):

  batch        -> (pod,) data         DP
  seq_kv       -> data                SP: long-context KV/state sharding
  vocab/mlp/heads/q_heads -> model    TP
  embed        -> data (+pod)         FSDP (ZeRO-3-style weight sharding)
  experts      -> None                expert-sliced TP (see DESIGN.md)
  layers       -> None                (scan axis)
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


DEFAULT_RULES: dict[str, tuple[str, ...] | None] = {
    "batch": ("pod", "data"),
    "rows": ("data",),      # KRR sample dim (streaming Nystrom / pipeline)
    "models": ("model",),   # independent-work dim: h/lam candidates, tenants
    "seq": None,            # activation sequence dim (sharded only for SP configs)
    "seq_kv": ("data",),    # KV-cache / SSM-state sequence dim for long decode
    "embed": ("data",),     # FSDP axis for weights' d_model dim
    "embed_pod": ("pod", "data"),  # FSDP over pods too (>=12B params)
    "vocab": ("model",),
    "mlp": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": None,
    "experts": None,
    "ssm_heads": ("model",),
    "ssm_state": None,
    "conv": None,
    "layers": None,
    "norm": None,
}


def _filter(axes: Optional[tuple[str, ...]], mesh: Mesh):
    if axes is None:
        return None
    present = tuple(a for a in axes if a in mesh.axis_names)
    if not present:
        return None
    return present if len(present) > 1 else present[0]


class Activation:
    def __init__(self, mesh: Mesh, rules: dict):
        self.mesh = mesh
        self.rules = rules

    def spec(self, logical_axes: Sequence[Optional[str]],
             shape: Optional[Sequence[int]] = None) -> P:
        """Logical axes -> PartitionSpec.

        Each mesh axis is used at most once (first dim wins), and when
        ``shape`` is provided, mesh axes that do not evenly divide a dim are
        dropped (replicated) — explicit input shardings require divisibility.
        """
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        used: set[str] = set()
        parts = []
        for i, ax in enumerate(logical_axes):
            rule = _filter(self.rules.get(ax, None) if ax else None, self.mesh)
            if rule is None:
                parts.append(None)
                continue
            axes = (rule,) if isinstance(rule, str) else tuple(rule)
            axes = tuple(a for a in axes if a not in used)
            if shape is not None and axes:
                factor = 1
                for a in axes:
                    factor *= sizes[a]
                while axes and shape[i] % factor != 0:
                    factor //= sizes[axes[-1]]
                    axes = axes[:-1]
            if not axes:
                parts.append(None)
                continue
            used.update(axes)
            parts.append(axes if len(axes) > 1 else axes[0])
        return P(*parts)

    def sharding(self, logical_axes: Sequence[Optional[str]],
                 shape: Optional[Sequence[int]] = None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical_axes, shape))


def active() -> Optional[Activation]:
    return getattr(_state, "activation", None)


@contextlib.contextmanager
def activate(mesh: Mesh, rules: dict | None = None):
    """Install mesh + logical rules for model code run within the context,
    with ``mesh`` set as the jit mesh.  Every mesh axis must be
    ``AxisType.Auto`` (build meshes through `repro.launch.mesh`)."""
    if any(t != AxisType.Auto for t in mesh.axis_types):
        raise ValueError(
            f"activate() needs AxisType.Auto mesh axes, got "
            f"{dict(zip(mesh.axis_names, mesh.axis_types))}; build the mesh "
            f"with repro.launch.mesh or jax.make_mesh(..., axis_types=...)")
    prev = getattr(_state, "activation", None)
    _state.activation = Activation(mesh, dict(DEFAULT_RULES, **(rules or {})))
    try:
        with jax.set_mesh(mesh):
            yield _state.activation
    finally:
        _state.activation = prev


def constrain(x: jax.Array, logical_axes: Sequence[Optional[str]]) -> jax.Array:
    """with_sharding_constraint against the active rules; no-op otherwise."""
    act = active()
    if act is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, act.sharding(logical_axes, x.shape))


def param_sharding(axes_tree, params_tree=None):
    """Map a tree of logical-axis tuples to NamedShardings (active mesh)."""
    act = active()
    if act is None:
        raise RuntimeError("param_sharding requires an active mesh (activate())")
    return jax.tree.map(
        lambda axes: act.sharding(axes),
        axes_tree,
        is_leaf=lambda t: isinstance(t, tuple) and all(
            a is None or isinstance(a, str) for a in t),
    )
