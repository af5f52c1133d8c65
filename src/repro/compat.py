"""Mesh helpers over the installed jax (0.9) API.

Every caller goes through this module, so a later jax rename is one
edit: ``jax.set_mesh`` activates a mesh, ``jax.sharding.get_abstract_mesh``
reads it back, and ``jax.shard_map`` maps over it.
"""
from __future__ import annotations

import jax


def mesh_context(mesh):
    """Context manager that activates ``mesh`` for the enclosed region:
    ``with mesh_context(mesh): ...``."""
    return jax.set_mesh(mesh)


def get_active_mesh():
    """The mesh activated by :func:`mesh_context`, or ``None``.  The
    result has ``axis_names`` and ``axis_sizes``."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return None
    return mesh


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with the repo's keyword spelling."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )
