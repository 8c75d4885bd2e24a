"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Single pod: (16, 16) = 256 chips (data, model).
Multi-pod: (2, 16, 16) = 512 chips (pod, data, model) — the ``pod`` axis is
pure data parallelism across pods (hierarchical FedAvg psum).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Whatever-fits mesh for CPU smoke tests (n devices -> (n/model, model))."""
    n = len(jax.devices())
    data = max(1, n // model)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def mesh_info(mesh) -> "MeshInfo":
    from repro.models.zoo import MeshInfo
    return MeshInfo(axis_names=tuple(mesh.axis_names),
                    axis_sizes={a: s for a, s in
                                zip(mesh.axis_names, mesh.devices.shape)})
