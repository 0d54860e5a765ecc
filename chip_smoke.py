"""Drive the Weaver served path once on a TPU and check every answer.

    python chip_smoke.py [--seed S] [--chips 4]

One chip (the default): the paper's deployment (``PAPER_DEPLOYMENT``: 4
gatekeepers, 8 shards) with its packed stamp columns resident on the
device (``device_shard_columns=True``) and group-commit write windows.

1. load a 100k-user, ~1M-follow power-law graph generated from
   ``--seed`` through ``Weaver.run_tx``, 1,024 operations per
   transaction;
2. serve through ``GraphQueryServer``: the TAO mix of
   ``examples/social_serve.py`` (``read_frac=0.998``), one batch each of
   2-hop ``traverse``, ``reachable``, ``sssp``, ``get_edges`` and
   ``clustering``, and a burst of writes that the write window batches.
   Every read is re-run inside its completion callback by the scalar
   interpreter at the same stamp (``frontier.run_local(...,
   use_frontier=False)``): garbage collection makes a comparison after
   the run unsound.  Every acknowledged write is read back;
3. build one snapshot through the device plane and compare it with the
   per-object reference ``snapshot_arrays_python``, then run
   ``pagerank_ga`` and ``bfs_levels_ga`` on it against numpy.

``--chips 4`` runs only the mesh phase: the same graph with the column
plane spread over four chips, its masks compared bit for bit with a
one-chip plane and with the host's numpy masks, and a snapshot through
it compared with the reference.

Earlier lines are diagnostics (host-clock seconds, compile counts,
device memory).  The last line is one JSON object naming the device;
it is printed only when every check passed.  Without a TPU the script
exits non-zero before doing any work.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N_USERS = 100_000
AVG_DEGREE = 10
TX_OPS = 1024            # operations per load transaction
WRITE_WINDOW = 0.5e-3    # group-commit window, simulated seconds
N_MIX = 4000             # TAO-mix requests: ~8 writes at read_frac 0.998
BATCH = 4                # requests per heavy-program batch
N_WRITES = 64            # transactions in the write burst


def log(*parts) -> None:
    print(*parts, flush=True)


class CompileCounter:
    """Counts XLA backend compilations (persistent-cache hits skip the
    backend and are not counted)."""

    def __init__(self) -> None:
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


class ReadChecker:
    """Completion callbacks that re-run each read at its own stamp with
    the scalar interpreter and record any difference."""

    def __init__(self, w) -> None:
        self.w = w
        self.checked = 0
        self.mismatches = []

    def cb(self, name, entries, then=None):
        from repro.core import frontier

        def _cb(result, stamp, latency):
            ref, _ = frontier.run_local(self.w, name, entries, stamp,
                                        use_frontier=False)
            self.checked += 1
            if result != ref:
                self.mismatches.append((name, entries, result, ref))
            if then is not None:
                then(result)
        return _cb


def deployment(seed: int, n_chips: int = 1):
    from repro.configs import PAPER_DEPLOYMENT
    from repro.core import Weaver

    return Weaver(dataclasses.replace(
        PAPER_DEPLOYMENT, device_shard_columns=True, n_chips=n_chips,
        write_group_commit=WRITE_WINDOW, seed=seed))


def load(w, edges) -> list:
    """Vertices then edges through ``run_tx``, ``TX_OPS`` per tx."""
    vertices = sorted({v for e in edges for v in e})
    for ops, add in ((vertices, lambda tx, v: tx.create_vertex(v)),
                     (edges, lambda tx, e: tx.create_edge(*e))):
        for i in range(0, len(ops), TX_OPS):
            tx = w.begin_tx()
            for op in ops[i:i + TX_OPS]:
                add(tx, op)
            r = w.run_tx(tx, timeout=60.0)
            if not r.ok:
                raise RuntimeError(f"load transaction failed: {r.error}")
    return vertices


def drain(srv, what: str) -> None:
    srv.drain(timeout=120.0)
    if srv.inflight:
        raise RuntimeError(f"{what}: {srv.inflight} requests never completed")


def serve(w, vertices, rng, chk: ReadChecker) -> dict:
    from repro.data import synth
    from repro.runtime.server import GraphQueryServer

    srv = GraphQueryServer(w)
    acks = {"ok": 0, "aborted": 0}
    kinds = {"create_edge": 0, "delete_edge": 0}

    def on_tx(r) -> None:
        acks["ok" if r.ok else "aborted"] += 1

    # the TAO mix, all requests in flight at once
    for op in synth.tao_workload(rng, N_MIX, 0.998, vertices):
        kind, v = op["type"], op["v"]
        if kind in ("get_edges", "count_edges", "get_node"):
            entries = [(v, None)]
            srv.submit("prog", (kind, entries), chk.cb(kind, entries))
            continue
        kinds[kind] += 1
        tx = w.begin_tx()
        if kind == "create_edge":
            tx.create_edge(v, op["u"])
        else:
            cur = w.read_vertex(v)
            if cur and cur["edges"]:
                tx.delete_edge(v, next(iter(cur["edges"])))
            else:
                tx.set_vertex_prop(v, "touch", 1)
        srv.submit("tx", tx, on_tx)
    drain(srv, "TAO mix")
    if not acks["ok"]:
        raise RuntimeError(f"the TAO mix acknowledged no write ({kinds})")

    # one batch of each multi-hop / ragged program
    def pick():
        return vertices[int(rng.integers(0, len(vertices)))]

    batches = {
        "traverse": lambda: [(pick(), {"depth": 0, "max_depth": 2})],
        "reachable": lambda: [(pick(), {"target": pick()})],
        "sssp": lambda: [(pick(), {"target": pick(), "max_depth": 4})],
        "get_edges": lambda: [(pick(), None)],
        "clustering": lambda: [(pick(), {"phase": 0})],
    }
    for name, make in batches.items():
        for _ in range(BATCH):
            entries = make()
            srv.submit("prog", (name, entries), chk.cb(name, entries))
        drain(srv, name)

    # a write burst the group-commit window batches, then read it back
    written = []
    for _ in range(N_WRITES):
        src, dst = pick(), pick()
        tx = w.begin_tx()
        tx.create_edge(src, dst)
        srv.submit("tx", tx, lambda r, e=(src, dst): written.append((e, r)))
    drain(srv, "write burst")
    lost = []
    for (src, dst), r in written:
        if not r.ok:
            raise RuntimeError(f"write {src}->{dst} not acknowledged: "
                               f"{r.error}")

        def has_edge(edges, e=(src, dst)):
            if e[1] not in {d for _, d in edges}:
                lost.append(e)
        entries = [(src, None)]
        srv.submit("prog", ("get_edges", entries),
                   chk.cb("get_edges", entries, then=has_edge))
    drain(srv, "read-back")
    if lost:
        raise RuntimeError(f"acknowledged writes not read back: {lost[:3]}")
    c = w.counters()
    return {"mix_writes": kinds, "mix_tx_ok": acks["ok"],
            "mix_tx_aborted": acks["aborted"],
            "burst_writes": len(written), "tx_batches": c["tx_batches"],
            "conflict_rows_checked": c["conflict_rows_checked"]}


def snapshot_stamp(w, vertices):
    """A live program stamp: snapshots are taken at it before the
    simulator runs again, so garbage collection cannot move past it."""
    _, stamp, _ = w.run_program("get_node", [(vertices[0], None)])
    return stamp


def canon(ga) -> list:
    return sorted(zip((ga.vids[i] for i in ga.edge_src.tolist()),
                      (ga.vids[i] for i in ga.edge_dst.tolist())))


def check_snapshot(w, at) -> dict:
    """Device-plane snapshot == per-object reference; graph kernels on
    it == numpy."""
    import jax
    import numpy as np

    from repro.core import analytics

    t0 = time.perf_counter()
    ga = analytics.SnapshotEngine(w).snapshot(at)
    t_snap = time.perf_counter() - t0
    ref = analytics.snapshot_arrays_python(w, at)
    if canon(ga) != canon(ref):
        raise RuntimeError("device snapshot differs from the reference")

    src, dst, n = ga.edge_src, ga.edge_dst, ga.n_nodes
    t0 = time.perf_counter()
    pr = np.asarray(jax.block_until_ready(analytics.pagerank_ga(ga)))
    lv = np.asarray(jax.block_until_ready(analytics.bfs_levels_ga(ga, [0])))
    t_kern = time.perf_counter() - t0

    deg = np.maximum(np.bincount(src, minlength=n), 1).astype(np.float64)
    want = np.full(n, 1.0 / n)
    for _ in range(20):
        want = 0.15 / n + 0.85 * np.bincount(dst, want[src] / deg[src],
                                             minlength=n)
    # float32 segment sums in another order: relative error of a few
    # hundred float32 ulps at most
    if not np.allclose(pr, want, rtol=1e-4, atol=1e-9):
        raise RuntimeError("pagerank differs from numpy: max abs err "
                           f"{np.abs(pr - want).max()}")
    levels = np.full(n, analytics.INF, np.int64)
    levels[0], frontier, d = 0, np.array([0]), 0
    indptr = ga.indptr
    while frontier.size:
        d += 1
        nbrs = np.concatenate([dst[indptr[v]:indptr[v + 1]]
                               for v in frontier.tolist()])
        nbrs = np.unique(nbrs[levels[nbrs] == analytics.INF])
        levels[nbrs] = d
        frontier = nbrs
    if not np.array_equal(lv.astype(np.int64), levels):
        raise RuntimeError("bfs levels differ from numpy")
    return {"snapshot_vertices": n, "snapshot_edges": int(src.size),
            "snapshot_s": t_snap, "pagerank_bfs_s": t_kern,
            "bfs_reached": int((levels != analytics.INF).sum())}


def served_path(seed: int, n_users: int = N_USERS) -> dict:
    import jax
    import numpy as np

    from repro.data import synth
    from repro.kernels.mv_visibility import ops

    rng = np.random.default_rng(seed)
    edges = synth.social_graph(rng, n_users=n_users, avg_degree=AVG_DEGREE)
    compiles = CompileCounter()
    w = deployment(seed)
    t0 = time.perf_counter()
    vertices = load(w, edges)
    t_load = time.perf_counter() - t0
    log(f"loaded {len(vertices)} vertices, {len(edges)} edges in "
        f"{t_load:.2f} s host clock ({len(edges) / t_load:.0f} edges/s), "
        f"{compiles.n} XLA compilations")

    chk = ReadChecker(w)
    c0 = compiles.n
    t0 = time.perf_counter()
    out = serve(w, vertices, rng, chk)
    t_serve = time.perf_counter() - t0
    log(f"served in {t_serve:.2f} s host clock: {out}, "
        f"{compiles.n - c0} XLA compilations")
    log(f"reads checked {chk.checked}, mismatched {len(chk.mismatches)}")
    if chk.mismatches:
        name, entries, got, ref = chk.mismatches[0]
        raise RuntimeError(f"{len(chk.mismatches)} reads differ from the "
                           f"scalar reference, first {name} {entries}: "
                           f"{got!r} != {ref!r}")

    snap = check_snapshot(w, snapshot_stamp(w, vertices))
    log(f"snapshot matches the reference: {snap}")
    log(f"device plane {w.device_plane.stats}; mv_visibility dispatches "
        f"{dict(ops.CALLS)}; XLA compilations in all {compiles.n}")
    mem = jax.devices()[0].memory_stats() or {}
    log(f"device bytes_in_use {mem.get('bytes_in_use')} "
        f"peak {mem.get('peak_bytes_in_use')}")
    if not w.device_plane.stats["launches"]:
        raise RuntimeError("the device plane never launched")
    if not ops.CALLS["compiled"]:
        raise RuntimeError("no compiled mv_visibility dispatch")
    return {"edges": len(edges), "load_s": t_load, "serve_s": t_serve}


def mesh_phase(seed: int, n_chips: int, n_users: int = N_USERS) -> dict:
    """The column plane over ``n_chips`` devices against a one-device
    plane and the host masks, then a snapshot through it."""
    import jax
    import numpy as np

    from repro.core import clock
    from repro.data import synth
    from repro.dist.columns import DeviceColumnPlane
    from repro.launch.mesh import make_columns_mesh

    rng = np.random.default_rng(seed)
    edges = synth.social_graph(rng, n_users=n_users, avg_degree=AVG_DEGREE)
    w = deployment(seed, n_chips)
    t0 = time.perf_counter()
    vertices = load(w, edges)
    log(f"loaded {len(vertices)} vertices, {len(edges)} edges in "
        f"{time.perf_counter() - t0:.2f} s host clock")
    at = snapshot_stamp(w, vertices)
    q = clock.pack(at, w.cfg.n_gatekeepers)
    cols = [sh.partition.columns for sh in w.shards if sh.alive]
    wide = w.device_plane
    if wide.n_dev != n_chips:
        raise RuntimeError(f"plane spans {wide.n_dev} devices, not {n_chips}")
    one = DeviceColumnPlane(w.cfg.n_gatekeepers, mesh=make_columns_mesh(1))
    for plane in (wide, one):
        plane.sync(cols)
        plane.before_all(q)
    homes = sorted({d.id for a in wide._dev for d in a.devices()})
    log(f"{len(cols)} shard blocks on devices {homes}, "
        f"{wide._spd} per device, {wide._cap} rows per table")
    if len(homes) != n_chips:
        raise RuntimeError(f"blocks sit on {len(homes)} devices only")
    for c in cols:
        host = (clock._np_before(c.v_create.view(), q),
                clock._np_before(c.v_delete.view(), q),
                clock._np_before(c.e_create.view(), q),
                clock._np_before(c.e_delete.view(), q))
        for a, b, h in zip(wide.masks_for(c), one.masks_for(c), host):
            if not (np.array_equal(a, b) and np.array_equal(a, h)):
                raise RuntimeError("sharded masks differ")
    log(f"masks of {n_chips} devices == 1 device == host numpy "
        f"for {len(cols)} shards")
    snap = check_snapshot(w, at)
    log(f"snapshot through the {n_chips}-device plane matches the "
        f"reference: {snap}")
    for d in jax.devices()[:n_chips]:
        log(f"device {d.id} bytes_in_use "
            f"{(d.memory_stats() or {}).get('bytes_in_use')}")
    return {"edges": len(edges)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = p.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"devices", file=sys.stderr)
        return 1

    from repro.kernels import backend
    log(f"platform {dev.platform} device_kind {dev.device_kind} "
        f"devices {len(devices)}; compile cache "
        f"{backend.use_compile_cache()}")
    if args.chips == 4:
        mesh_phase(args.seed, 4)
    else:
        served_path(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
