"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips ("data","model");
multi-pod: 2x16x16 = 512 chips ("pod","data","model").
"""
from __future__ import annotations

import jax


def _mesh_kwargs(n_axes: int) -> dict:
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_mesh_kwargs(len(axes)))


def make_local_mesh() -> jax.sharding.Mesh:
    """Whatever devices exist (1 on the CPU test container)."""
    n = len(jax.devices())
    return jax.make_mesh((1, n), ("data", "model"), **_mesh_kwargs(2))
