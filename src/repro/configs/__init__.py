"""Architecture registry (the four GNNs of the dynamic-graph training
path, looked up by ``get_arch(<id>)``) and the paper system's own
deployment config.
"""

from __future__ import annotations

from typing import Dict

from .base import ArchSpec, ShapeSpec

from . import dimenet, gat_cora, gin_tu, pna
from .weaver_store import PAPER_DEPLOYMENT

_MODULES = [gin_tu, pna, dimenet, gat_cora]

ARCHS: Dict[str, ArchSpec] = {m.SPEC.arch_id: m.SPEC for m in _MODULES}


def get_arch(arch_id: str) -> ArchSpec:
    if arch_id not in ARCHS:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(ARCHS)}")
    return ARCHS[arch_id]


__all__ = ["ARCHS", "get_arch", "ArchSpec", "ShapeSpec", "PAPER_DEPLOYMENT"]
