"""The jax 0.9 mesh and shard_map surface, in one module.

* :func:`mesh_context` — ``jax.set_mesh``.
* :func:`shard_map` — ``jax.shard_map`` with its ``check_vma`` flag.
* :func:`make_mesh` — ``jax.make_mesh`` with every axis explicitly Auto.

``repro.distributed.ctx`` and ``repro.launch.mesh`` re-export these for
their existing call sites; new code should import ``repro.compat``
directly.
"""

from __future__ import annotations

import jax


def mesh_context(mesh):
    """Context manager that makes ``mesh`` the ambient mesh."""
    return jax.set_mesh(mesh)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=True):
    """``jax.shard_map``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
