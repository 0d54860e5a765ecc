"""Distributed utilities: the device column plane (:mod:`.columns`) and
the process-global mesh registry the GNN models consult.

The GNN forward functions call :func:`constrain`, which is an identity
while no mesh is installed (single device) and a sharding constraint
under a mesh installed with :func:`set_mesh`.  Spec arguments to
:func:`constrain` are FUNCTIONS of the mesh (e.g.
``lambda m: P(tuple(m.axis_names), None)``) so model code never has to
know which axes exist in the current deployment.
"""

from __future__ import annotations

from typing import Callable

_MESH = None


def set_mesh(mesh) -> None:
    """Install the process-global mesh (None to clear)."""
    global _MESH
    _MESH = mesh


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
