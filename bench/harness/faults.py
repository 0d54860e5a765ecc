"""Faults and controls planted under a run, to show that the check fails
them.  Used by ``bench/tests`` and ``bench/tools/control.py``, never by
``bench/run.py``.

Each is a context manager that patches the program under test for its
duration:

* ``ack_without_apply`` — a write is acknowledged and never applied (a
  step that returns its state unchanged);
* ``ack_half_without_apply`` — every other transaction is acknowledged
  and dropped (half of the batch left out);
* ``alter_answer`` — each edge-list or edge-count read loses one edge
  where the answer is produced;
* ``alter_pagerank`` — one vertex's PageRank score doubles;
* ``control_stale_reads`` — the serving control: every point read is
  answered from a copy of the committed state taken the first time its
  vertex is read and never refreshed (a read cache without invalidation;
  it breaks strict serializability, the guarantee the social
  configurations state);
* ``control_bf16_pagerank`` — the analytics control: the plain PageRank
  put in the program's place in bfloat16, the precision below the
  configuration's float32.
"""

from __future__ import annotations

import contextlib
from typing import Callable, Dict


@contextlib.contextmanager
def _patch(obj, name: str, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _ack(weaver, callback) -> None:
    from repro.core.txn import TxResult

    weaver.sim.schedule(1e-4, callback, TxResult(ok=True, latency=1e-4))


@contextlib.contextmanager
def ack_without_apply():
    from repro.core.weaver import Weaver

    def submit_tx(self, tx, callback, gatekeeper=None):
        _ack(self, callback)
    with _patch(Weaver, "submit_tx", submit_tx):
        yield


@contextlib.contextmanager
def ack_half_without_apply():
    from repro.core.weaver import Weaver

    real = Weaver.submit_tx
    n = {"i": 0}

    def submit_tx(self, tx, callback, gatekeeper=None):
        n["i"] += 1
        if n["i"] % 2:
            return _ack(self, callback)
        return real(self, tx, callback, gatekeeper)
    with _patch(Weaver, "submit_tx", submit_tx):
        yield


@contextlib.contextmanager
def alter_answer():
    from repro.runtime.server import GraphQueryServer

    real = GraphQueryServer.submit

    def submit(self, kind, payload, on_done=None):
        if kind == "prog" and payload[0] in ("get_edges", "count_edges") \
                and on_done is not None:
            def altered(result, stamp, latency, _cb=on_done):
                if isinstance(result, list) and result:
                    result = result[:-1]
                elif isinstance(result, int):
                    result = max(0, result - 1)
                _cb(result, stamp, latency)
            return real(self, kind, payload, altered)
        return real(self, kind, payload, on_done)
    with _patch(GraphQueryServer, "submit", submit):
        yield


@contextlib.contextmanager
def alter_pagerank():
    import jax.numpy as jnp

    from repro.core import analytics

    real = analytics.pagerank_ga

    def pagerank_ga(ga, n_iters=20, damping=0.85):
        pr = real(ga, n_iters, damping)
        return pr.at[0].multiply(jnp.float32(2.0))
    with _patch(analytics, "pagerank_ga", pagerank_ga):
        yield


@contextlib.contextmanager
def control_stale_reads():
    from repro.runtime.server import GraphQueryServer

    real = GraphQueryServer.submit
    frozen: Dict[str, dict] = {}

    def submit(self, kind, payload, on_done=None):
        if kind != "prog" or payload[0] not in ("get_edges", "count_edges",
                                                "get_node"):
            return real(self, kind, payload, on_done)
        name, entries = payload
        v = entries[0][0]
        if v not in frozen:
            frozen[v] = self.weaver.read_vertex(v)
        cur = frozen[v]
        edges = cur["edges"] if cur else {}
        if name == "get_edges":
            result = sorted(edges.items())
        elif name == "count_edges":
            result = len(edges)
        else:
            result = {"id": v, "n_edges": len(edges)} if cur else None
        self.inflight += 1

        def done():
            self.inflight -= 1
            if on_done:
                on_done(result, None, 0.0)
        self.weaver.sim.schedule(5e-4, done)
    with _patch(GraphQueryServer, "submit", submit):
        yield


def _pagerank_bf16(src, dst, n: int, iters: int, damping: float):
    import jax
    import jax.numpy as jnp

    dt = jnp.bfloat16
    deg = jnp.maximum(jax.ops.segment_sum(jnp.ones(src.shape, dt), src,
                                          num_segments=n), 1)
    pr = jnp.full((n,), 1.0 / n, dt)
    for _ in range(iters):
        agg = jax.ops.segment_sum(pr[src] / deg[src], dst, num_segments=n)
        pr = (jnp.asarray(1.0 - damping, dt) / n
              + jnp.asarray(damping, dt) * agg).astype(dt)
    return pr.astype(jnp.float32)


@contextlib.contextmanager
def control_bf16_pagerank():
    import jax
    import jax.numpy as jnp

    from repro.core import analytics

    fn = jax.jit(_pagerank_bf16, static_argnums=(2, 3, 4))

    def pagerank_ga(ga, n_iters=20, damping=0.85):
        return fn(jnp.asarray(ga.csc_src), jnp.asarray(ga.csc_dst),
                  ga.n_nodes, int(n_iters), float(damping))
    with _patch(analytics, "pagerank_ga", pagerank_ga):
        yield


FAULTS: Dict[str, Callable] = {
    "ack_without_apply": ack_without_apply,
    "ack_half_without_apply": ack_half_without_apply,
    "alter_answer": alter_answer,
    "alter_pagerank": alter_pagerank,
    "control_stale_reads": control_stale_reads,
    "control_bf16_pagerank": control_bf16_pagerank,
}
