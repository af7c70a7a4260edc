"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count on first backend init — see dryrun.py's
XLA_FLAGS preamble)."""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_host_mesh", "make_fleet_mesh",
           "replica_axes", "fleet_size", "tp_size"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Uses the first prod(shape) devices so the single-pod mesh also builds in
    a 512-placeholder-device dry-run process."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import numpy as np
    n = int(np.prod(shape))
    devs = np.asarray(jax.devices()[:n]).reshape(shape)
    return jax.sharding.Mesh(
        devs, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 4, model: int = 2):
    """Small mesh for multi-device host tests (XLA_FLAGS device_count=8)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def make_fleet_mesh(fleet: int = 2, model: int = 2):
    """Mesh for D-PSGD on real models: node-parameters shard their leading
    node axis over 'fleet' (``train.shardings.node_param_specs``) and each
    node's tensors shard over 'model' (the TP rules), so node count and
    model size scale independently. ``fleet * model`` must not exceed the
    visible device count (multi-device CPU CI gets 8 via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``)."""
    avail = jax.device_count()
    if fleet * model > avail:
        raise ValueError(
            f"fleet mesh needs {fleet}x{model}={fleet * model} devices but "
            f"only {avail} are visible (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N before "
            "importing jax)")
    return jax.make_mesh((fleet, model), ("fleet", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def replica_axes(mesh) -> tuple[str, ...]:
    """The D-PSGD node axes = every axis except 'model'."""
    return tuple(a for a in mesh.axis_names if a != "model")


def fleet_size(mesh) -> int:
    """Number of node slots: the product of the ``replica_axes`` sizes."""
    size = 1
    for a in replica_axes(mesh):
        size *= int(mesh.shape[a])
    return size


def tp_size(mesh) -> int:
    """TP degree of the mesh — 1 when it carries no 'model' axis."""
    return int(mesh.shape["model"]) if "model" in mesh.axis_names else 1
