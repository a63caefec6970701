"""Small jax-version compatibility shims for the parallel/optim layers."""
from __future__ import annotations

from typing import Optional

import jax


def _static_mesh_size(name: str) -> Optional[int]:
    """Size of axis ``name`` on the ambient mesh (a ``with mesh:``
    context), resolvable *outside* any shard_map/pmap trace."""
    try:
        from jax.interpreters import pxla

        mesh = pxla.thread_resources.env.physical_mesh
        if mesh is not None and name in getattr(mesh, "shape", {}):
            return int(mesh.shape[name])
    except Exception:
        pass
    try:  # newer jax: sharding-context abstract mesh
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is not None and name in getattr(mesh, "shape", {}):
            return int(mesh.shape[name])
    except Exception:
        pass
    return None


def axis_size(name: str, mesh=None) -> int:
    """Static size of the named mesh axis, inside *or* outside shard_map.

    Resolution order: an explicitly passed ``mesh``; the bound axis of
    the enclosing shard_map/pmap trace (``jax.lax.axis_size``); finally
    the ambient mesh of a ``with mesh:`` context, so helpers like the
    collective-matmul kernels and ZeRO-1 sharding arithmetic work when
    called at trace level too."""
    if mesh is not None and name in getattr(mesh, "shape", {}):
        return int(dict(mesh.shape)[name])
    try:
        return int(jax.lax.axis_size(name))
    except NameError:
        pass
    size = _static_mesh_size(name)
    if size is not None:
        return size
    raise NameError(
        f"unbound axis name {name!r}: not inside shard_map/pmap and no "
        "ambient mesh (`with mesh:`) defines it")
