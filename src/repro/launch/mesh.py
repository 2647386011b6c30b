"""Production meshes.  A FUNCTION (not a module-level constant) so importing
this module never touches jax device state — jax locks the device count on
first backend init, and only dryrun.py is allowed to force 512 host devices.

Every mesh states ``AxisType.Auto`` axes: the logical-rule constraints of
`repro.distributed.sharding` are sharding hints, which JAX's default
Explicit axes refuse.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, names, devices=None):
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False,
                         model_parallelism: int = 16,
                         num_devices: int | None = None):
    """2D (data, model) mesh — or 3D (pod, data, model) for ``multi_pod`` —
    derived from the visible device count.

    ``model_parallelism`` sizes the "model" axis (the independent-work axis:
    calibrate h/lam candidates, per-tenant batched fits — see
    `repro.core.streaming`'s "models" rule); the "data" axis takes whatever
    remains, so the same call scales from a forced-host-device test rig to a
    full pod without editing a hardcoded shape.  ``num_devices`` pins the
    chip budget explicitly (dryrun.py uses it to model fixed pod sizes on a
    forced 512-device host); the default uses every visible device.
    """
    n_dev = int(num_devices) if num_devices is not None else len(jax.devices())
    pods = 2 if multi_pod else 1
    if model_parallelism < 1:
        raise ValueError(f"model_parallelism must be >= 1, "
                         f"got {model_parallelism}")
    denom = pods * model_parallelism
    if n_dev % denom != 0:
        raise ValueError(
            f"cannot build a {'multi-pod ' if multi_pod else ''}mesh from "
            f"{n_dev} devices with model_parallelism={model_parallelism}"
            f"{' and 2 pods' if multi_pod else ''}: {n_dev} is not divisible "
            f"by {denom}. Pick a model_parallelism that divides the device "
            f"count (or pass num_devices= to use a subset).")
    data = n_dev // denom
    if multi_pod:
        return _auto_mesh((pods, data, model_parallelism),
                          ("pod", "data", "model"))
    return _auto_mesh((data, model_parallelism), ("data", "model"))


def make_local_mesh(axis: str = "data", devices=None):
    """``devices`` (default: all addressable devices) on one axis."""
    devices = list(jax.devices() if devices is None else devices)
    return _auto_mesh((len(devices),), (axis,), devices=devices)


def make_local_mesh_2d(model_parallelism: int = 2):
    """All addressable devices as a (data, model) grid — the forced-host-
    device test shape (e.g. 4 devices -> (2, 2) under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``)."""
    n = len(jax.devices())
    if model_parallelism < 1 or n % model_parallelism != 0:
        raise ValueError(
            f"cannot split {n} devices into a (data, model) grid with "
            f"model_parallelism={model_parallelism}: pick a divisor of the "
            f"device count")
    return _auto_mesh((n // model_parallelism, model_parallelism),
                      ("data", "model"))
