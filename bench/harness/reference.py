"""Plain reference for a graph store under the configurations' guarantees.

It imports nothing of the program under test and takes nothing that the
program made: it is fed the data the benchmark generated and the writes
it submitted, with the times (on the simulated cluster's clock) at which
each was issued and acknowledged.

* :class:`Adjacency` is the out-edge state of every vertex,
  ``{src: {eid: dst}}``.  Edge ids are the handles the client API hands
  back when a write is built, so the benchmark knows them before
  submitting.
* :func:`check_reads` holds every read to strict serializability: a
  write acknowledged before the read was issued must be visible, a write
  issued after the read completed must not be, and a write in flight
  during the read may or may not be (every subset is tried).
* :class:`EdgeLog` keeps the analytics cell's edges as arrays with the
  request at which each appeared and vanished, so the state after any
  request can be rebuilt; :func:`pagerank`, :func:`bfs_levels` and
  :func:`edge_keys` are the analytics references in float64 numpy.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

READS = ("get_edges", "count_edges", "get_node")
WRITES = ("create_edge", "delete_edge")

#: subsets of in-flight writes tried per read before the read is judged
#: a mismatch (2**MAX_MAYBE states)
MAX_MAYBE = 10


@dataclass
class Write:
    kind: str                    # "create_edge" | "delete_edge"
    src: str
    eid: int
    dst: Optional[str]           # None for a delete
    t_issue: float               # simulated seconds
    t_ack: Optional[float] = None
    ok: Optional[bool] = None    # None: never acknowledged


@dataclass
class Read:
    kind: str
    v: str
    t_issue: float
    t_done: Optional[float] = None
    result: object = None


class Adjacency:
    """``{src: {eid: dst}}`` for every vertex that exists."""

    def __init__(self, vertices: Sequence[str]) -> None:
        self.out: Dict[str, Dict[int, str]] = {v: {} for v in vertices}

    def add(self, src: str, eid: int, dst: str) -> None:
        self.out[src][eid] = dst

    def remove(self, src: str, eid: int) -> None:
        del self.out[src][eid]

    def apply(self, w: Write) -> None:
        if w.kind == "create_edge":
            self.add(w.src, w.eid, w.dst)
        else:
            self.remove(w.src, w.eid)


def expected(kind: str, v: str, edges: Optional[Dict[int, str]]):
    """What a read of ``kind`` at ``v`` answers over ``edges`` (None when
    the vertex does not exist)."""
    if kind == "get_edges":
        return sorted(edges.items()) if edges is not None else []
    if kind == "count_edges":
        return len(edges) if edges is not None else 0
    if kind == "get_node":
        return None if edges is None else {"id": v, "n_edges": len(edges)}
    raise ValueError(f"unknown read kind {kind!r}")


def normalise(kind: str, result):
    """The program's answer in the reference's form."""
    if kind == "get_edges":
        return sorted((int(e), d) for e, d in (result or []))
    if kind == "count_edges":
        return int(result or 0)
    return result


def _states(base: Dict[int, str], must: List[Write], maybe: List[Write]):
    edges = dict(base)
    for w in must:
        _apply(edges, w)
    for k in range(len(maybe) + 1):
        for subset in itertools.combinations(maybe, k):
            cur = dict(edges)
            ok = True
            for w in subset:
                ok = _apply(cur, w) and ok
            if ok:
                yield cur


def _apply(edges: Dict[int, str], w: Write) -> bool:
    if w.kind == "create_edge":
        edges[w.eid] = w.dst
        return True
    return edges.pop(w.eid, None) is not None


def check_reads(base: Adjacency, writes: List[Write],
                reads: List[Read]) -> Tuple[int, int, list]:
    """(reads checked, reads that no serializable state explains, the
    first few of those).  ``base`` is the state before the first of
    ``writes``; a read that never completed is not checked here (it
    counts as failed)."""
    by_src: Dict[str, List[Write]] = {}
    for w in writes:
        by_src.setdefault(w.src, []).append(w)
    checked, bad, first = 0, 0, []
    for r in reads:
        if r.t_done is None:
            continue
        checked += 1
        hist = by_src.get(r.v, [])
        must = [w for w in hist if w.ok and w.t_ack < r.t_issue]
        must.sort(key=lambda w: w.t_ack)
        maybe = [w for w in hist
                 if w.t_issue <= r.t_done and w.ok is not False
                 and not (w.ok and w.t_ack < r.t_issue)]
        got = normalise(r.kind, r.result)
        exists = r.v in base.out
        ok = False
        if len(maybe) <= MAX_MAYBE:
            for st in _states(base.out.get(r.v, {}), must, maybe):
                if got == expected(r.kind, r.v, st if exists else None):
                    ok = True
                    break
        if not ok:
            bad += 1
            if len(first) < 3:
                first.append((r.kind, r.v, got))
    return checked, bad, first


# ---------------------------------------------------------------- analytics
@dataclass
class EdgeLog:
    """Directed edges with the request at which each appeared (``born``,
    0 for the loaded graph) and vanished (``dead``, a large number while
    alive).  Edge ``i``'s id is ``eids[i]``."""

    n_vertices: int
    src: np.ndarray
    dst: np.ndarray
    eids: np.ndarray
    born: np.ndarray = None
    dead: np.ndarray = None
    _where: Dict[int, int] = field(default_factory=dict)

    ALIVE = np.iinfo(np.int64).max

    def __post_init__(self) -> None:
        n = self.src.size
        if self.born is None:
            self.born = np.zeros(n, np.int64)
        if self.dead is None:
            self.dead = np.full(n, self.ALIVE, np.int64)
        self._where = {int(e): i for i, e in enumerate(self.eids.tolist())}

    def add(self, request: int, src: np.ndarray, dst: np.ndarray,
            eids: np.ndarray) -> None:
        base = self.src.size
        self.src = np.concatenate([self.src, src])
        self.dst = np.concatenate([self.dst, dst])
        self.eids = np.concatenate([self.eids, eids])
        self.born = np.concatenate(
            [self.born, np.full(src.size, request, np.int64)])
        self.dead = np.concatenate(
            [self.dead, np.full(src.size, self.ALIVE, np.int64)])
        for i, e in enumerate(eids.tolist()):
            self._where[int(e)] = base + i

    def kill(self, request: int, eids: Sequence[int]) -> None:
        for e in eids:
            self.dead[self._where[int(e)]] = request

    def at(self, request: int) -> Tuple[np.ndarray, np.ndarray]:
        """(src, dst) of the edges alive once ``request`` has committed."""
        m = (self.born <= request) & (self.dead > request)
        return self.src[m], self.dst[m]


def edge_keys(src: np.ndarray, dst: np.ndarray, n: int) -> np.ndarray:
    """Sorted ``src * n + dst`` keys: the edge multiset in one array."""
    return np.sort(src.astype(np.int64) * n + dst.astype(np.int64))


def multiset_diff(a: np.ndarray, b: np.ndarray) -> int:
    """Edges in one sorted key multiset and not the other."""
    if a.size == b.size and np.array_equal(a, b):
        return 0
    ua, ca = np.unique(a, return_counts=True)
    ub, cb = np.unique(b, return_counts=True)
    keys = np.union1d(ua, ub)
    na = np.zeros(keys.size, np.int64)
    nb = np.zeros(keys.size, np.int64)
    na[np.searchsorted(keys, ua)] = ca
    nb[np.searchsorted(keys, ub)] = cb
    return int(np.abs(na - nb).sum())


def pagerank(src: np.ndarray, dst: np.ndarray, n: int, iters: int,
             damping: float) -> np.ndarray:
    """PageRank as the configuration states it: ``iters`` synchronous
    iterations from ``1/n``, out-degree floored at 1, dangling mass not
    redistributed.  float64."""
    deg = np.maximum(np.bincount(src, minlength=n), 1).astype(np.float64)
    pr = np.full(n, 1.0 / n)
    for _ in range(iters):
        pr = (1.0 - damping) / n + damping * np.bincount(
            dst, pr[src] / deg[src], minlength=n)
    return pr


def bfs_levels(src: np.ndarray, dst: np.ndarray, n: int,
               root: int) -> np.ndarray:
    """Hop count from ``root`` along directed edges; -1 unreachable."""
    order = np.argsort(src, kind="stable")
    s, d = src[order], dst[order]
    indptr = np.searchsorted(s, np.arange(n + 1))
    level = np.full(n, -1, np.int64)
    level[root] = 0
    frontier = np.array([root], np.int64)
    hop = 0
    while frontier.size:
        hop += 1
        starts, ends = indptr[frontier], indptr[frontier + 1]
        lens = ends - starts
        if not lens.sum():
            break
        idx = np.repeat(starts - np.cumsum(lens) + lens, lens) \
            + np.arange(lens.sum())
        nbrs = np.unique(d[idx])
        nbrs = nbrs[level[nbrs] < 0]
        level[nbrs] = hop
        frontier = nbrs
    return level
