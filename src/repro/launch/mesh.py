"""Production meshes.

Single pod : (data=16, model=16)      = 256 chips (TPU v5e pod slice)
Multi-pod  : (pod=2, data=16, model=16) = 512 chips

Defined as functions (never module-level constants) so importing this
module cannot touch jax device state. Axes are `Auto`: the model code
places arrays with sharding constraints, which an `Explicit` axis (the
`jax.make_mesh` default since JAX 0.9) would turn into assertions.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def mesh_chips(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
