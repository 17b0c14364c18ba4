"""The one module that touches JAX's sharding API surface.

The rest of ``repro`` builds meshes, abstract meshes and ``shard_map``
programs through these wrappers, so a JAX upgrade that moves one of
these names breaks exactly one module (enforced by the
``repro.analysis`` invariant linter).  Written against the installed
JAX 0.9 API: one call per shim.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import AbstractMesh, AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types on every axis."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def abstract_mesh(shape: Sequence[int],
                  axes: Sequence[str]) -> AbstractMesh:
    """Device-free mesh for spec/tracing logic."""
    return AbstractMesh(tuple(shape), tuple(axes))


def get_abstract_mesh():
    """The abstract mesh of the current sharding context, or ``None``
    outside any mesh context (JAX returns an empty mesh there)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return None
    return mesh


def use_mesh(mesh: jax.sharding.Mesh):
    """Context manager activating ``mesh`` for the enclosed region."""
    return jax.set_mesh(mesh)


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map``; ``check`` maps to its ``check_vma``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def cost_analysis(compiled) -> Dict[str, Any]:
    """``compiled.cost_analysis()`` as a plain dict (empty when the
    backend reports nothing)."""
    return dict(compiled.cost_analysis() or {})


def mesh_axis_sizes(mesh, axes: Optional[Tuple[str, ...]] = None
                    ) -> Tuple[int, ...]:
    """Sizes of ``axes`` (default: all axes) on a Mesh or AbstractMesh."""
    names = tuple(mesh.axis_names) if axes is None else tuple(axes)
    return tuple(int(mesh.shape[a]) for a in names)
