"""Production mesh definitions.

A TPU v5e pod is modelled as a 16 x 16 chip mesh with named axes
(data, model); the multi-pod configuration adds an outer `pod` axis
(2 x 16 x 16 = 512 chips) for data parallelism across the DCN/ICI
boundary. Defined as functions so importing this module never touches
JAX device state (the dry-run pins XLA_FLAGS *before* first jax init).

Scaling posture: growing `pod` is pure outer data parallelism (gradient
all-reduce, optionally int8-compressed — optim.compression); nothing in
the sharding layer references the pod count, so N-pod launches reuse the
same specs.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: shardings come from in_shardings and propagation, as the
    # launch code was written for (Explicit axes reject plain gathers)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """1x1 mesh on the default device: the one-chip mesh for serving and
    training (and for CPU runs of the same launch code)."""
    return _auto_mesh((1, 1), ("data", "model"))


def dp_axes(mesh) -> tuple[str, ...]:
    """Batch-sharding axes of a mesh, outermost first."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def tp_axis(mesh) -> str:
    return "model"
