"""Mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required so tests/benches see 1 CPU device
while dryrun.py sees 512 forced host devices.

Every mesh in the repo is built by ``make_mesh`` and installed with
``jax.set_mesh(mesh)`` as a context manager: ``constrain`` and the Pallas
``shard_map`` read it back at trace time through
``jax.sharding.get_abstract_mesh()``.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "batch_axes", "mesh_tag"]


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """A device mesh whose axes are all ``Auto``: GSPMD propagates shardings
    and ``with_sharding_constraint`` pins them.  ``jax.make_mesh`` defaults
    to ``Explicit`` axes, which refuse ``with_sharding_constraint``."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) ("data", "model") = 256 chips (TPU v5e pod).
    Multi-pod: (2, 16, 16) ("pod", "data", "model") = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def batch_axes(mesh) -> Tuple[str, ...]:
    """Mesh axes that carry the global batch (DP)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def mesh_tag(mesh) -> str:
    return "x".join(str(s) for s in mesh.devices.shape)
