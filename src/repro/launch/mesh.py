"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state — a caller may set
XLA_FLAGS (a described device count) before any jax initialisation.

Every builder makes ``Auto`` axes: the model and training code shard
through ``with_sharding_constraint`` and leave propagation to the
compiler, which ``jax.make_mesh``'s default ``Explicit`` axes refuse
(gathers and scans over sharded operands stop type-checking).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh for CPU smoke tests/examples."""
    return make_mesh((1, 1), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Mesh axes that act as pure data parallelism (pod is DP-only)."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)
