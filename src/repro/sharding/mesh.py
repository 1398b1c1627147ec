"""Mesh construction.

Since JAX 0.9, ``jax.make_mesh`` gives Explicit axes unless told
otherwise. The model code shards by GSPMD annotation
(``with_sharding_constraint`` in ``sharding/rules.py``) and by
``jax.shard_map``, and both need Auto axes, so every mesh the repo builds
comes from here.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> Mesh:
    """A device mesh with Auto axes."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def auto_axes(mesh: Mesh) -> Mesh:
    """The same devices and axis names as ``mesh``, with Auto axes (for
    callers that hand in a mesh from ``jax.make_mesh``'s default)."""
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def make_abstract_mesh(axis_sizes: Sequence[int],
                       axis_names: Sequence[str]) -> AbstractMesh:
    """A device-free mesh (Auto axes) for spec and shape tests."""
    sizes = tuple(int(s) for s in axis_sizes)
    names = tuple(str(n) for n in axis_names)
    if len(sizes) != len(names):
        raise ValueError(f"axis_sizes/axis_names length mismatch: "
                         f"{sizes} vs {names}")
    return AbstractMesh(sizes, names)
