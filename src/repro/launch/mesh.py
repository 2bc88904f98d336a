"""Production mesh builders (single-pod 16x16 and 2-pod 2x16x16).

Defined as FUNCTIONS so importing this module never touches jax device
state (device count is locked at first jax init; the dry-run sets
XLA_FLAGS before importing anything else).

Every axis is ``Auto``: the programs here place arrays with
``NamedSharding``/``with_sharding_constraint`` and let the partitioner
propagate, which ``jax.make_mesh``'s default ``Explicit`` axes refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests use small CPU meshes, e.g. (4, 2))."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def data_axes(mesh) -> tuple:
    """All batch-parallel axes of a mesh ('pod' is outer data parallelism)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh):
    return "model" if "model" in mesh.axis_names else None


def all_axes(mesh) -> tuple:
    return tuple(mesh.axis_names)
