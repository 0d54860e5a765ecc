"""Serving launcher: ``python -m repro.launch.serve``.

Boots a Weaver deployment, loads a synthetic social graph and serves the
TAO read/write mix (the paper's native serving workload).
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def serve_graph(args) -> int:
    from repro.configs import PAPER_DEPLOYMENT
    from repro.core import Weaver
    from repro.data import synth
    from repro.runtime import GraphQueryServer

    w = Weaver(PAPER_DEPLOYMENT)
    rng = np.random.default_rng(args.seed)
    edges = synth.social_graph(rng, n_users=args.users, avg_degree=8)
    vertices = sorted({v for e in edges for v in e})
    # bulk load in chunks of transactions
    for i in range(0, len(vertices), 64):
        tx = w.begin_tx()
        for v in vertices[i:i + 64]:
            tx.create_vertex(v)
        assert w.run_tx(tx).ok
    for i in range(0, len(edges), 64):
        tx = w.begin_tx()
        for s, d in edges[i:i + 64]:
            tx.create_edge(s, d)
        assert w.run_tx(tx).ok

    server = GraphQueryServer(w)
    ops = synth.tao_workload(rng, args.requests, read_frac=0.998,
                             vertices=vertices)
    t0 = w.sim.now
    for op in ops:
        if op["type"] in ("get_edges", "count_edges", "get_node"):
            server.submit("prog", (op["type"], [(op["v"], None)]))
        elif op["type"] == "create_edge":
            tx = w.begin_tx()
            tx.create_edge(op["v"], op["u"])
            server.submit("tx", tx)
        else:
            ed = w.read_vertex(op["v"])
            if ed and ed["edges"]:
                tx = w.begin_tx()
                tx.delete_edge(op["v"], next(iter(ed["edges"])))
                server.submit("tx", tx)
    server.drain(timeout=30.0)
    dt = w.sim.now - t0
    done = len(server.completed)
    print(f"served {done}/{len(ops)} requests in {dt:.3f}s simulated "
          f"-> {done / max(dt, 1e-9):,.0f} req/s")
    c = w.counters()
    print(f"oracle calls: {c['oracle_calls']}, announce msgs: "
          f"{c['announce_messages']}, committed tx: {c['tx_committed']}")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--users", type=int, default=200)
    ap.add_argument("--requests", type=int, default=500)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    return serve_graph(args)


if __name__ == "__main__":
    sys.exit(main())
