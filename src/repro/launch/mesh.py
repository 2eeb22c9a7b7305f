"""Mesh construction.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state.  Single pod: 16×16 = 256 chips (v5e pod),
axes ("data", "model").  Multi-pod: 2×16×16 = 512 chips, axes
("pod", "data", "model") — the "pod" axis carries pure data parallelism
across the inter-pod links (DCN in practice; the dry-run proves the
program shards over it).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """Every mesh the program builds.  Its axes are ``Auto``: the model
    pins activations and gradients with ``with_sharding_constraint``,
    which ``jax.make_mesh``'s default ``Explicit`` axes refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """All data-parallel axes of a mesh (everything except "model")."""
    return tuple(a for a in mesh.axis_names if a != "model")


def n_chips(mesh: jax.sharding.Mesh) -> int:
    return mesh.devices.size
