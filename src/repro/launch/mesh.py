"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (v5e pod);
multi-pod: 2x16x16 = 512 chips with a leading "pod" axis.  Mesh creation
goes through :func:`repro.compat.make_mesh` (all-Auto axes).
"""
from __future__ import annotations

from .. import compat

__all__ = ["make_production_mesh", "make_mesh_for"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_mesh_for(shape, axes):
    return compat.make_mesh(tuple(shape), tuple(axes))
