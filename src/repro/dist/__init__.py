"""Distributed utilities: mesh registry + sharding-constraint helpers.

The models reference a process-global mesh so the same forward functions
run unmodified on a single CPU device (mesh unset -> every helper is an
identity / trivial answer) and under `jax.jit` on a production mesh
(`launch.dryrun` calls :func:`set_mesh` before lowering).

Spec arguments to :func:`constrain` are FUNCTIONS of the mesh (e.g.
``lambda m: P(dp_axes(m), "model")``) so model code never has to know
which axes exist in the current deployment.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Union

from . import collectives, sharding

_MESH = None


def set_mesh(mesh) -> None:
    """Install the process-global mesh (None to clear)."""
    global _MESH
    _MESH = mesh


def get_mesh():
    """The installed mesh, or None (single-device / smoke-test mode)."""
    return _MESH


def axis_size(mesh, axes: Union[None, str, Sequence[str]]) -> int:
    """Product of the named mesh axis sizes (1 when unset/empty)."""
    if mesh is None or axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= int(mesh.shape[a])
    return size


def constrain(x, spec_fn: Callable):
    """Apply ``with_sharding_constraint(x, spec_fn(mesh))`` under the
    global mesh; identity when no mesh is installed."""
    mesh = _MESH
    if mesh is None:
        return x
    import jax
    from jax.sharding import NamedSharding

    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec_fn(mesh)))

