"""Thin wrappers over the jax mesh/sharding API (jax 0.9).

Everything mesh-shaped in this repo goes through these helpers, so the
repo's calls to the explicit-sharding API (``jax.sharding.AxisType``,
``jax.make_mesh(..., axis_types=...)``, ``AbstractMesh(shape, names)``,
``jax.shard_map``) live in one module.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh

__all__ = ["AxisType", "make_mesh", "make_abstract_mesh",
           "default_axis_types", "cost_analysis", "shard_map"]


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              *, axis_types: Optional[Sequence[AxisType]] = None,
              devices=None) -> Mesh:
    """``jax.make_mesh`` from sequences, with optional axis types."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(tuple(axis_types)
                                     if axis_types is not None else None),
                         devices=devices)


def make_abstract_mesh(axis_shapes: Sequence[int],
                       axis_names: Sequence[str]) -> AbstractMesh:
    """``AbstractMesh`` from parallel shape/name sequences."""
    return AbstractMesh(tuple(axis_shapes), tuple(axis_names))


def default_axis_types(n: int) -> Tuple[AxisType, ...]:
    """``(AxisType.Auto,) * n`` — the repo-wide default for every mesh."""
    return (AxisType.Auto,) * n


def cost_analysis(compiled) -> dict:
    """Per-device cost dict of a compiled executable ({} for trivial
    programs, where jax returns None)."""
    return compiled.cost_analysis() or {}


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with the repo's keyword-only calling convention."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
