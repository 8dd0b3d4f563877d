"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state; the dry-run entrypoint sets XLA_FLAGS before importing anything.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the launch code places arrays
    with ``with_sharding_constraint``, which only accepts Auto axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2 pods x 256 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host actually has (CPU smoke runs): 1D data mesh."""
    n = len(jax.devices())
    return _auto_mesh((n,), ("data",))


def batch_axes(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
