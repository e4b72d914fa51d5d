"""Production mesh builders.

Functions, not module constants: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

import jax

# Auto axes: shardings come from the state/batch spec trees and activation
# constraints, not from jax's explicit-sharding types (make_mesh's default,
# under which with_sharding_constraint asserts).
_AUTO = (jax.sharding.AxisType.Auto,)


def make_production_mesh(*, multi_pod: bool = False):
    """The assigned production mesh: 16x16 chips per pod; 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_AUTO * len(axes))


def make_host_mesh(model_axis: int = 1):
    """Single-host debug mesh over the locally visible devices."""
    n = jax.device_count()
    assert n % model_axis == 0
    return jax.make_mesh((n // model_axis, model_axis), ("data", "model"),
                         axis_types=_AUTO * 2)


def dp_axes(mesh) -> tuple[str, ...]:
    """Mesh axes that jointly form the data-parallel dimension."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)


def axis_size(mesh, axes) -> int:
    size = 1
    for a in (axes if isinstance(axes, (tuple, list)) else (axes,)):
        size *= mesh.shape[a]
    return size
