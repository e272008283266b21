"""Mesh context + in-graph batch anchoring.

`mesh_context(mesh)` establishes the active mesh for a region of code;
`constrain_batch(x, *rest)` is the model-side anchor: inside a mesh
context it pins dim 0 of an activation to the batch (data) axes and the
remaining dims to the given axis names, and outside any mesh (the
single-device test/CPU path) it is an exact no-op. Model code can
therefore call it unconditionally.

Only `mesh_context` installs a mesh here; a bare `with mesh:` block is
not seen. Meshes come from launch/mesh.py:make_mesh, whose Auto axes
accept the sharding constraints below.
"""
from __future__ import annotations

import contextlib

import jax
from jax.sharding import NamedSharding

_MESH_STACK: list = []


def current_mesh():
    """The innermost mesh installed by `mesh_context`, else None."""
    return _MESH_STACK[-1] if _MESH_STACK else None


@contextlib.contextmanager
def mesh_context(mesh):
    """Install `mesh` as the active mesh (stacked; reentrant)."""
    _MESH_STACK.append(mesh)
    try:
        with jax.set_mesh(mesh):
            yield mesh
    finally:
        _MESH_STACK.pop()


def constrain_batch(x, *rest):
    """Anchor activation `x`: dim 0 on the batch (data) axes, dims 1..n on
    the given axis names (None = unsharded). No-op without a mesh or on a
    1-device mesh. Extra/missing `rest` entries are padded with None."""
    from repro.dist.sharding import batch_pspec

    mesh = current_mesh()
    if mesh is None or mesh.devices.size <= 1:
        return x
    names = tuple(rest) + (None,) * (x.ndim - 1 - len(rest))
    spec = batch_pspec(mesh, x.shape[0], names[:x.ndim - 1])
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
