"""Production mesh builders (TPU v5e: 16x16 chips/pod; pods over DCN)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _axis_kwargs(n: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (roofline) or 2x16x16 multi-pod (dry run)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kwargs(len(axes)))


def make_mesh(shape, axes):
    """Arbitrary mesh with pjit-style auto sharding propagation."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         **_axis_kwargs(len(axes)))


def mesh_context(mesh):
    """Scope ``mesh`` as the ambient mesh (``jax.set_mesh``)."""
    return jax.set_mesh(mesh)


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes of a production mesh (includes pod)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def chips(mesh) -> int:
    return mesh.devices.size
