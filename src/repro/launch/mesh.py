"""Production meshes.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state. Single-pod: 16×16 =
256 chips ("data", "model"); multi-pod: 2×16×16 = 512 chips
("pod", "data", "model") — the pod axis is an extra data-parallel /
pipeline dimension that crosses the inter-pod DCI links.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh_shape"]


def make_mesh_shape(*, multi_pod: bool = False):
    if multi_pod:
        return (2, 16, 16), ("pod", "data", "model")
    return (16, 16), ("data", "model")


def make_production_mesh(*, multi_pod: bool = False):
    shape, axes = make_mesh_shape(multi_pod=multi_pod)
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — the "
            "dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count"
            " BEFORE importing jax (see launch/dryrun.py)"
        )
    return jax.make_mesh(
        shape, axes, devices=devices[:n],
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )
