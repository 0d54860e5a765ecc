"""GNN models (pure-functional JAX) and their message-passing seam."""

from . import gnn, mp

__all__ = ["gnn", "mp"]
