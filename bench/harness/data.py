"""Seeded data and access generators, vectorised numpy.

Every generator returns the same sizes for every seed, so that a seed
changes which vertices and edges there are and never how many: the
compiled shapes, the load and the work of a run are the same from one
seed to the next.

* :func:`power_law_edges` — the follower graph of the social
  configurations: source and destination ranks drawn independently with
  weight ``rank ** -exponent`` (the shape of ``synth.social_graph``),
  self-loops redrawn, ``round(n * avg_degree)`` edges.
* :func:`rmat_edges` — Graph500's R-MAT/Kronecker generator (initiator
  ``a, b, c, d``, ``edge_factor << scale`` edges, vertex labels
  permuted), kept as Graphalytics keeps it: undirected, without
  self-loops or duplicates.  The first ``n_undirected`` distinct edges in
  generation order are kept, more being drawn if a seed falls short.
* :class:`Zipf` — ranks drawn with weight ``rank ** -theta`` over a
  permutation of the vertices.

A configuration names its generator under ``data.generator``;
:data:`GENERATORS` maps that name to a :class:`Graph` builder, and
:func:`graph` refuses a name it does not hold.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import numpy as np


def vertex_names(prefix: str, n: int) -> list:
    return [f"{prefix}{i}" for i in range(n)]


def power_law_edges(rng: np.random.Generator, n: int, avg_degree: float,
                    exponent: float) -> Tuple[np.ndarray, np.ndarray]:
    m = int(round(n * avg_degree))
    cdf = np.cumsum(1.0 / np.arange(1, n + 1) ** exponent)
    cdf /= cdf[-1]
    src = np.searchsorted(cdf, rng.random(m), side="right")
    dst = np.searchsorted(cdf, rng.random(m), side="right")
    loops = np.nonzero(src == dst)[0]
    while loops.size:
        dst[loops] = np.searchsorted(cdf, rng.random(loops.size),
                                     side="right")
        loops = loops[src[loops] == dst[loops]]
    return src.astype(np.int64), dst.astype(np.int64)


def rmat_pairs(rng: np.random.Generator, scale: int, m: int,
               abcd: Tuple[float, float, float, float],
               perm: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """``m`` R-MAT edges over ``2**scale`` vertices, labels permuted."""
    a, b, c, _ = abcd
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for bit in range(scale):
        r = rng.random(m)
        src |= (r >= a + b).astype(np.int64) << bit
        dst |= (((r >= a) & (r < a + b)) | (r >= a + b + c)).astype(
            np.int64) << bit
    return perm[src], perm[dst]


def rmat_edges(rng: np.random.Generator, scale: int, edge_factor: int,
               abcd, n_undirected: int
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(lo, hi, perm): ``n_undirected`` distinct undirected edges
    ``lo < hi`` in generation order, and the label permutation."""
    n = 1 << scale
    perm = rng.permutation(n)
    keys = np.zeros(0, np.int64)
    while True:
        s, d = rmat_pairs(rng, scale, edge_factor << scale, abcd, perm)
        keep = s != d
        lo, hi = np.minimum(s, d)[keep], np.maximum(s, d)[keep]
        keys = np.concatenate([keys, lo * n + hi])
        _, first = np.unique(keys, return_index=True)
        if first.size >= n_undirected:
            break
    picked = keys[np.sort(first)[:n_undirected]]
    return picked // n, picked % n, perm


class Zipf:
    """Vertex ranks with weight ``rank ** -theta``; rank ``r`` is vertex
    ``order[r]``."""

    def __init__(self, n: int, theta: float, order: np.ndarray) -> None:
        cdf = np.cumsum(1.0 / np.arange(1, n + 1) ** theta)
        self.cdf = cdf / cdf[-1]
        self.order = order

    def draw(self, rng: np.random.Generator, k: int) -> np.ndarray:
        r = np.searchsorted(self.cdf, rng.random(k), side="right")
        return self.order[np.minimum(r, self.order.size - 1)]


@dataclass
class Graph:
    """A configuration's graph as generated: ``n`` vertices, the edges
    ``(src[i], dst[i])``, loaded in both directions when ``undirected``,
    and ``draw(rng, k)``, which gives ``k`` more edges of the same
    generator (self-loops included) for churn."""

    n: int
    src: np.ndarray
    dst: np.ndarray
    undirected: bool
    draw: Callable[[np.random.Generator, int], Tuple[np.ndarray, np.ndarray]]

    def edges(self) -> Tuple[np.ndarray, np.ndarray]:
        """The directed edges as loaded: an undirected graph's edges
        first one way, then, in the same order, the other."""
        if not self.undirected:
            return self.src, self.dst
        return (np.concatenate([self.src, self.dst]),
                np.concatenate([self.dst, self.src]))


def _power_law(rng: np.random.Generator, g: dict,
               scale: Optional[float]) -> Graph:
    n = g["n_vertices"] if scale is None else \
        max(64, int(g["n_vertices"] * scale))
    deg, ex = float(g["avg_degree"]), float(g["exponent"])
    src, dst = power_law_edges(rng, n, deg, ex)
    cdf = np.cumsum(1.0 / np.arange(1, n + 1) ** ex)
    cdf /= cdf[-1]

    def draw(r: np.random.Generator, k: int):
        return (np.searchsorted(cdf, r.random(k), side="right"),
                np.searchsorted(cdf, r.random(k), side="right"))
    return Graph(n, src, dst, False, draw)


def _rmat(rng: np.random.Generator, g: dict, scale: Optional[float]) -> Graph:
    s = g["scale"] if scale is None else int(scale)
    n = 1 << s
    m = g["n_undirected"] if scale is None else \
        int(g["n_undirected"] * n / (1 << g["scale"]))
    abcd = tuple(g["initiator"])
    lo, hi, perm = rmat_edges(rng, s, int(g["edge_factor"]), abcd, m)

    def draw(r: np.random.Generator, k: int):
        return rmat_pairs(r, s, k, abcd, perm)
    return Graph(n, lo, hi, True, draw)


#: ``data.generator`` -> builder ``(rng, data, scale) -> Graph``.  ``scale``
#: shrinks the data for a rehearsal on the CPU: a share of the vertices
#: for ``power_law``, an R-MAT scale for ``rmat``; None is the size the
#: configuration states.
GENERATORS = {"power_law": _power_law, "rmat": _rmat}


def graph(rng: np.random.Generator, g: dict,
          scale: Optional[float] = None) -> Graph:
    gen = g.get("generator")
    if gen not in GENERATORS:
        raise ValueError(f"no data generator {gen!r}; known: "
                         f"{sorted(GENERATORS)}")
    return GENERATORS[gen](rng, g, scale)
