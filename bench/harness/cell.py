"""One run of one cell: set-up, warm-up, the measured window, the check.

The cluster is the program's discrete-event simulator, driven by this
one host process as fast as the host allows but never ahead of the wall
clock: :class:`Pacer` runs ``sim.run(until=...)`` in slices of
``SLICE_S`` simulated seconds, each capped at the simulated time equal
to the wall time since the window began.  Requests enter through
``GraphQueryServer.submit``; a closed-loop client issues its next
request from the completion callback of its last one.  Latency is
measured on the host's clock from issue to completion callback.  An
analytics request is one blocking call sequence here: its churn and its
stamp go through the simulator, then the snapshot and the graph kernels
run while the simulator waits, with their results on the host.

Two runners exist, chosen by the request kinds of the mix; both drive
the closed loop, the one arrival loop (a mix's ``"loop"``) there is:

* ``serve`` — point reads and edge writes (``reference.READS`` and
  ``reference.WRITES``);
* ``analytics`` — the ``analytics`` kind: churn, fresh snapshot,
  pagerank and BFS.

Correctness is decided against ``reference`` once the window has closed:
every read of the window against the writes the benchmark submitted,
every acknowledged write read back, and in the analytics loop a seeded
sample of requests' snapshots, pageranks and BFS levels against float64
numpy over the benchmark's own edge log.
"""

from __future__ import annotations

import contextlib
import os
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from . import compiles, data, reference, traffic
from . import trace as btrace
from .spec import ROOT, Cell

sys.path.insert(0, os.path.join(ROOT, "src"))

#: simulated seconds per pacing slice: small against a point read's
#: simulated path (about 0.7 ms), so the slice never sets a latency
SLICE_S = 0.1e-3
#: wall seconds to wait after the window for requests still in flight
DRAIN_S = 60.0
#: loading: operations per transaction, and transactions outstanding
LOAD_TX_OPS = 1024
LOAD_INFLIGHT = 4


def log(*parts) -> None:
    print(*parts, flush=True)


class Spans:
    """Host-clock spans from the benchmark's own files, around its calls
    into each layer; in a traced run also profiler annotations named
    ``bench.<name>``."""

    def __init__(self, traced: bool) -> None:
        self.traced = traced
        self.total: Dict[str, float] = defaultdict(float)
        self.count: Dict[str, int] = defaultdict(int)

    @contextmanager
    def span(self, name: str):
        if self.traced:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        else:
            ann = nullcontext()
        t0 = time.perf_counter()
        with ann:
            yield
        self.total[name] += time.perf_counter() - t0
        self.count[name] += 1

    def reset(self) -> None:
        self.total.clear()
        self.count.clear()


class Pacer:
    """Runs the simulator in ``SLICE_S`` slices, never past the simulated
    time equal to the wall time since :meth:`start`."""

    def __init__(self, sim, spans: Spans) -> None:
        self.sim = sim
        self.spans = spans
        self.start()

    def start(self) -> None:
        self.w0 = time.perf_counter()
        self.s0 = self.sim.now

    def run_while(self, cond: Callable[[], bool],
                  wall_deadline: Optional[float] = None) -> bool:
        sim = self.sim
        while cond():
            now = time.perf_counter()
            if wall_deadline is not None and now >= wall_deadline:
                return False
            until = min(sim.now + SLICE_S, self.s0 + (now - self.w0))
            if until <= sim.now:
                time.sleep(min(1e-3, sim.now - until + 1e-6))
                continue
            with self.spans.span("sim_run"):
                sim.run(until=until)
        return True


@dataclass
class RunRecord:
    """What the metric readers read: host-clock times of the window, the
    program's counters and the device plane's stats at its two ends, the
    benchmark's spans, and the reduced trace of a traced run."""

    cell: str
    setup_s: float = 0.0
    window_s: float = 0.0
    kinds: List[str] = field(default_factory=list)     # per request
    latency_s: List[Optional[float]] = field(default_factory=list)
    done_in_window: int = 0
    span_s: float = 0.0          # window start to the last completion
    counters0: dict = field(default_factory=dict)
    counters1: dict = field(default_factory=dict)
    sim0: float = 0.0
    sim1: float = 0.0
    plane0: dict = field(default_factory=dict)
    plane1: dict = field(default_factory=dict)
    spans: Optional[Spans] = None
    trace: Optional[dict] = None
    device_kind: str = ""


# ---------------------------------------------------------------- set-up
def deployment(cfg: dict, seed: int):
    """The configuration's Weaver: every number it states is set here,
    so a change to the program's defaults does not move the cell."""
    from repro.core import Weaver
    from repro.core.gatekeeper import CostModel
    from repro.core.simulation import NetworkModel
    from repro.core.weaver import WeaverConfig

    d = cfg["deployment"]
    return Weaver(WeaverConfig(
        cost=CostModel(**d["cost"]), network=NetworkModel(**d["network"]),
        seed=seed, **d["weaver"]))


def load(w, vertices: List[str], src: List[str],
         dst: List[str]) -> np.ndarray:
    """Vertices, then edges, through ``submit_tx``: ``LOAD_TX_OPS``
    operations per transaction, ``LOAD_INFLIGHT`` transactions
    outstanding, a transaction that aborts built again and resubmitted.
    Returns the edges' ids."""
    eids = np.empty(len(src), np.int64)

    def vertex_tx(lo: int, hi: int):
        tx = w.begin_tx()
        for v in vertices[lo:hi]:
            tx.create_vertex(v)
        return tx

    def edge_tx(lo: int, hi: int):
        tx = w.begin_tx()
        for j in range(lo, hi):
            eids[j] = tx.create_edge(src[j], dst[j]).eid
        return tx

    for make, n in ((vertex_tx, len(vertices)), (edge_tx, len(src))):
        todo = [(i, min(i + LOAD_TX_OPS, n), 0)
                for i in range(0, n, LOAD_TX_OPS)]
        todo.reverse()
        pending: list = []
        while todo or pending:
            while todo and len(pending) < LOAD_INFLIGHT:
                lo, hi, tries = todo.pop()
                box: list = []
                w.submit_tx(make(lo, hi), box.append)
                pending.append((lo, hi, tries, box))
            w.sim.run(until=w.sim.now + 1e-3)
            still = []
            for lo, hi, tries, box in pending:
                if not box:
                    still.append((lo, hi, tries, box))
                elif not box[0].ok:
                    if tries >= 8:
                        raise RuntimeError(
                            f"load transaction failed: {box[0].error}")
                    todo.append((lo, hi, tries + 1))
            pending = still
    return eids


def _persistent_cache() -> str:
    import jax

    from repro.kernels import backend

    path = backend.use_compile_cache()
    # every program goes to the cache, however fast it compiled, so a
    # run after the first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ----------------------------------------------------------------- serve
class Serve:
    """Closed-loop point reads and edge writes against the social
    configurations, recorded for the reference."""

    def __init__(self, cell: Cell, seed: int, scale: Optional[float],
                 spans: Spans) -> None:
        cfg, g = cell.config, cell.config["data"]
        gr = data.graph(np.random.default_rng([seed, 0]), g, scale)
        n = self.n = gr.n
        s, d = gr.edges()
        self.names = data.vertex_names(g["prefix"], n)
        names = self.names
        self.src = [names[i] for i in s.tolist()]
        self.dst = [names[i] for i in d.tolist()]
        acc = cfg["access"]
        order = np.random.default_rng(acc["permutation_seed"]).permutation(n)
        self.zipf = data.Zipf(n, acc["theta"], order)
        self.rng = np.random.default_rng([seed, 1])
        self.mix = cell.mix
        self.clients = int(cell.mix["clients"])
        self.weights = traffic.shares(cell.mix)
        self.spans = spans
        self.w = deployment(cfg, seed)

    def setup(self) -> None:
        from repro.runtime.server import GraphQueryServer

        t0 = time.perf_counter()
        eids = load(self.w, self.names, self.src, self.dst)
        log(f"loaded {self.n} vertices, {len(self.src)} edges in "
            f"{time.perf_counter() - t0:.2f} s")
        self.adj = reference.Adjacency(self.names)
        for s, e, d in zip(self.src, eids.tolist(), self.dst):
            self.adj.add(s, e, d)
        del self.src, self.dst
        self.srv = GraphQueryServer(self.w)
        self.pacer = Pacer(self.w.sim, self.spans)
        self.pending_del: set = set()
        self.touched: set = set()
        self.saved: Dict[str, Dict[int, str]] = {}
        self.recording = False

    # -- requests
    def _issue(self, req, done: Callable) -> None:
        """Submit one request; ``done(ok, ref_record)`` on completion."""
        w, sim = self.w, self.w.sim
        v = self.names[req.v]
        if req.kind in reference.READS:
            rec = reference.Read(req.kind, v, sim.now)

            def on_read(result, stamp, latency):
                rec.t_done = sim.now
                rec.result = result
                done(True, rec)
            with self.spans.span("submit"):
                self.srv.submit("prog", (req.kind, [(v, None)]), on_read)
            return
        tx = w.begin_tx()
        if req.kind == "create_edge":
            u = self.names[req.u if req.u != req.v else (req.v + 1) % self.n]
            h = tx.create_edge(v, u)
            rec = reference.Write("create_edge", v, h.eid, u, sim.now)
        elif req.kind == "delete_edge":
            v, eid = self._victim(v)
            self.pending_del.add(eid)
            tx.delete_edge(v, eid)
            rec = reference.Write("delete_edge", v, eid, None, sim.now)
        else:
            raise ValueError(f"the serve loop has no request kind "
                             f"{req.kind!r}")
        self.touched.add(v)

        def on_tx(r):
            rec.t_ack, rec.ok = sim.now, bool(r.ok)
            if rec.kind == "delete_edge":
                self.pending_del.discard(rec.eid)
            if r.ok:
                if rec.src not in self.saved and self.recording:
                    self.saved[rec.src] = dict(self.adj.out[rec.src])
                self.adj.apply(rec)
            done(bool(r.ok), rec)
        with self.spans.span("submit"):
            self.srv.submit("tx", tx, on_tx)

    def _victim(self, v: str):
        """The oldest acknowledged edge of ``v`` not already being
        deleted; other vertices from the access distribution when ``v``
        has none."""
        for _ in range(256):
            live = [e for e in self.adj.out[v] if e not in self.pending_del]
            if live:
                return v, min(live)
            v = self.names[int(self.zipf.draw(self.rng, 1)[0])]
        raise RuntimeError("no edge left to delete")

    def _loop(self, weights, t_end: Optional[float], n_max: Optional[int],
              on_record: Callable) -> dict:
        """Closed loop of ``self.clients`` clients until the wall clock
        passes ``t_end`` or ``n_max`` requests were issued; then drain."""
        reqs = traffic.stream(weights, int(self.mix["block"]), self.rng,
                              self.zipf.draw)
        st = {"issued": 0, "inflight": 0}

        def issue() -> None:
            now = time.perf_counter()
            if (t_end is not None and now >= t_end) or \
                    (n_max is not None and st["issued"] >= n_max):
                return
            i = st["issued"]
            st["issued"] += 1
            st["inflight"] += 1
            req = next(reqs)
            t_issue = now

            def done(ok: bool, rec) -> None:
                st["inflight"] -= 1
                on_record(i, req.kind, t_issue, time.perf_counter(), ok,
                          rec)
                issue()
            on_record(i, req.kind, t_issue, None, None, None)
            self._issue(req, done)

        for _ in range(self.clients):
            issue()
        if t_end is not None:
            self.pacer.run_while(lambda: time.perf_counter() < t_end)
        deadline = time.perf_counter() + DRAIN_S
        self.pacer.run_while(lambda: st["inflight"] > 0,
                             wall_deadline=deadline)
        return st

    def warmup(self, counter, warm: dict) -> None:
        """A burst of the mix's writes alone (every write path reached
        once), then rounds of the mix itself."""
        writes = {k: v for k, v in self.weights.items()
                  if k in reference.WRITES}

        def round_() -> str:
            self.pacer.start()
            if writes and not self.warmed:
                self._loop(writes, None, int(warm["write_burst"]),
                           lambda *a: None)
                self.warmed = True
            self._loop(self.weights, None, int(warm["requests"]),
                       lambda *a: None)
            return "round"
        self.warmed = False
        warm_up(counter, warm, round_)

    def window(self, rec: RunRecord, seconds: float) -> None:
        self.recording = True
        self.reads: List[reference.Read] = []
        self.writes: List[reference.Write] = []
        self.unanswered = 0

        def on_record(i, kind, t_issue, t_done, ok, ref):
            if t_done is None:
                rec.kinds.append(kind)
                rec.latency_s.append(None)
                return
            rec.latency_s[i] = t_done - t_issue
            if t_done <= t_end:
                rec.done_in_window += 1
            if isinstance(ref, reference.Read):
                self.reads.append(ref)
            else:
                self.writes.append(ref)

        self.pacer.start()
        t0 = time.perf_counter()
        t_end = t0 + seconds
        st = self._loop(self.weights, t_end, None, on_record)
        rec.window_s = seconds
        self.unanswered = st["inflight"]

    def check(self, rec: RunRecord) -> Dict[str, dict]:
        """Reads of the window against the writes submitted; every vertex
        written, read back once the cluster is quiet."""
        base = reference.Adjacency([])
        base.out = dict(self.adj.out)
        base.out.update(self.saved)
        n, bad, first = reference.check_reads(base, self.writes, self.reads)
        if first:
            log(f"reads unexplained by any serializable state: {first}")
        # quiesce, then read back every vertex a write touched
        sim = self.w.sim
        sim.run(until=sim.now + 50e-3)
        got: Dict[str, object] = {}
        for v in sorted(self.touched):
            self.srv.submit("prog", ("get_edges", [(v, None)]),
                            lambda r, s, l, v=v: got.__setitem__(v, r))
        self.srv.drain(timeout=10.0)
        lost = sum(1 for v in self.touched
                   if v not in got or reference.normalise("get_edges",
                                                          got[v])
                   != reference.expected("get_edges", v, self.adj.out[v]))
        failed_w = sum(1 for x in self.writes if x.ok is not True)
        log(f"reads checked {n}, unexplained {bad}; writes "
            f"{len(self.writes)} ({failed_w} not acknowledged), "
            f"vertices read back {len(self.touched)}, differing {lost}")
        self.failed = failed_w + self.unanswered
        return {"read_mismatches": {"value": bad, "limit": 0},
                "readback_mismatches": {"value": lost, "limit": 0},
                "unanswered": {"value": self.unanswered, "limit": 0}}


# ------------------------------------------------------------- analytics
class Analytics:
    """One client: churn, a fresh snapshot, pagerank and BFS, back to
    back, on the Graph500 configurations."""

    def __init__(self, cell: Cell, seed: int, scale: Optional[float],
                 spans: Spans) -> None:
        cfg, g = cell.config, cell.config["data"]
        self.graph = data.graph(np.random.default_rng([seed, 0]), g, scale)
        self.n = self.graph.n
        self.names = data.vertex_names(g["prefix"], self.n)
        self.rng = np.random.default_rng([seed, 2])
        self.sample_rng = np.random.default_rng([seed, 3])
        self.a = cell.mix["analytics"]
        self.kernels = cfg["kernels"]
        self.limits = cfg["limits"]
        self.spans = spans
        self.w = deployment(cfg, seed)

    def setup(self) -> None:
        from repro.core import analytics
        from repro.runtime.server import GraphQueryServer

        names, gr = self.names, self.graph
        src, dst = gr.edges()
        t0 = time.perf_counter()
        eids = load(self.w, names, [names[i] for i in src.tolist()],
                    [names[i] for i in dst.tolist()])
        log(f"loaded {self.n} vertices, {src.size} edges in "
            f"{time.perf_counter() - t0:.2f} s")
        self.log = reference.EdgeLog(self.n, src, dst, eids)
        # live pairs (forward id, reverse id or -1, a, b): an undirected
        # graph's edge is both directions, written and deleted together
        m = gr.src.size
        rev = eids[m:] if gr.undirected else np.full(m, -1, np.int64)
        self.pairs = np.stack([eids[:m], rev, gr.src, gr.dst], 1)
        self.n_pairs = m
        deg = np.bincount(src, minlength=self.n)
        self.roots = np.nonzero(deg)[0]
        self.srv = GraphQueryServer(self.w)
        self.engine = analytics.SnapshotEngine(self.w)
        self.pacer = Pacer(self.w.sim, self.spans)
        self.k = 0
        self.history: list = []
        self.kept: list = []
        self.seen = 0

    def _churn(self):
        """This request's transactions: ``creates`` new edges of the
        configuration's generator and ``deletes`` live ones (an undirected
        edge both ways), spread evenly over ``churn_txs`` transactions."""
        a, both = self.a, self.graph.undirected
        n_tx = int(a["churn_txs"])
        pairs_per_tx = int(a["tx_ops"]) // (2 if both else 1)
        n_create = int(round(pairs_per_tx * float(a["create_share"])))
        n_delete = pairs_per_tx - n_create
        need = n_tx * n_create
        s, d = self.graph.draw(self.rng, 2 * need + 64)
        keep = s != d
        new = np.stack([s[keep], d[keep]], 1)[:need]
        want = n_tx * n_delete
        pick = np.zeros(0, np.int64)
        while pick.size < want:
            more = self.rng.integers(0, self.n_pairs, 2 * want)
            pick = np.concatenate([pick, more])
            _, first = np.unique(pick, return_index=True)
            pick = pick[np.sort(first)][:want]
        gone = self.pairs[pick].copy()
        # swap-remove the picked pairs from the live pool
        for i in np.sort(pick)[::-1].tolist():
            self.n_pairs -= 1
            self.pairs[i] = self.pairs[self.n_pairs]
        txs = []
        for t in range(n_tx):
            tx = self.w.begin_tx()
            made = []
            for a_, b_ in new[t * n_create:(t + 1) * n_create].tolist():
                x, y = self.names[a_], self.names[b_]
                made.append((tx.create_edge(x, y).eid,
                             tx.create_edge(y, x).eid if both else -1,
                             a_, b_))
            dead = gone[t * n_delete:(t + 1) * n_delete]
            for fe, re, a_, b_ in dead.tolist():
                tx.delete_edge(self.names[a_], fe)
                if both:
                    tx.delete_edge(self.names[b_], re)
            txs.append((tx, made, dead))
        return txs

    def request(self) -> dict:
        """One analytics request; returns what the check needs."""
        import jax

        from repro.core import analytics

        self.k += 1
        k = self.k
        txs = self._churn()
        acks: List[Optional[bool]] = [None] * len(txs)
        with self.spans.span("churn"):
            for i, (tx, _, _) in enumerate(txs):
                self.srv.submit("tx", tx,
                                lambda r, i=i: acks.__setitem__(i, r.ok))
            self.pacer.run_while(lambda: None in acks)
        failed = sum(1 for ok in acks if not ok)
        for ok, (_, made, dead) in zip(acks, txs):
            if not ok:
                continue
            # the reference's edge log is written after the window
            self.history.append((k, made, dead))
            if made:
                mm = np.array(made, np.int64)
                room = self.pairs.shape[0] - self.n_pairs
                if room < len(mm):
                    self.pairs = np.concatenate(
                        [self.pairs, np.zeros_like(self.pairs)])
                self.pairs[self.n_pairs:self.n_pairs + len(mm)] = mm
                self.n_pairs += len(mm)
        root = self.names[int(self.roots[
            self.rng.integers(0, self.roots.size)])]
        box: list = []
        with self.spans.span("stamp"):
            self.srv.submit("prog", ("get_node", [(root, None)]),
                            lambda r, s, l: box.append(s))
            self.pacer.run_while(lambda: not box)
        kp = self.kernels
        # the snapshot is taken before the simulator runs again, so
        # garbage collection cannot pass its stamp
        with self.spans.span("snapshot"):
            ga = self.engine.snapshot(box[0])
        with self.spans.span("kernels"):
            pr = np.asarray(jax.block_until_ready(analytics.pagerank_ga(
                ga, n_iters=int(kp["pagerank_iters"]),
                damping=float(kp["pagerank_damping"]))))
            lv = np.asarray(jax.block_until_ready(analytics.bfs_levels_ga(
                ga, [ga.index[root]])))
        return {"k": k, "failed": failed, "src": ga.edge_src,
                "dst": ga.edge_dst, "vids": ga.vids, "pr": pr, "lv": lv,
                "root": root}

    def warmup(self, counter, warm: dict) -> None:
        def round_() -> str:
            self.pacer.start()
            return f"failed txs {self.request()['failed']}"
        warm_up(counter, warm, round_)

    def window(self, rec: RunRecord, seconds: float) -> None:
        size = int(self.a["check_sample"])
        self.failed = 0
        self.pacer.start()
        t0 = time.perf_counter()
        t_end = t0 + seconds
        while time.perf_counter() < t_end:
            ts = time.perf_counter()
            out = self.request()
            te = time.perf_counter()
            rec.kinds.append("analytics")
            rec.latency_s.append(te - ts)
            self.failed += out["failed"] > 0
            if te <= t_end:
                rec.done_in_window += 1
            # reservoir sample of the window's requests, from the seed
            self.seen += 1
            if len(self.kept) < size:
                self.kept.append(out)
            else:
                j = int(self.sample_rng.integers(0, self.seen))
                if j < size:
                    self.kept[j] = out
        rec.window_s = seconds
        rec.span_s = time.perf_counter() - t0
        self.unanswered = 0

    def check(self, rec: RunRecord) -> Dict[str, dict]:
        n, kp = self.n, self.kernels
        both = self.graph.undirected
        for k, made, dead in self.history:
            if made:
                mm = np.array(made, np.int64)
                if both:
                    self.log.add(k, np.concatenate([mm[:, 2], mm[:, 3]]),
                                 np.concatenate([mm[:, 3], mm[:, 2]]),
                                 np.concatenate([mm[:, 0], mm[:, 1]]))
                else:
                    self.log.add(k, mm[:, 2], mm[:, 3], mm[:, 0])
            self.log.kill(k, np.concatenate([dead[:, 0], dead[:, 1]])
                          if both else dead[:, 0])
        snap = bfs = 0
        err = 0.0
        ids_of: Dict[int, np.ndarray] = {}
        for out in self.kept:
            vids = out["vids"]
            if id(vids) not in ids_of:
                ids_of[id(vids)] = np.array([int(v[1:]) for v in vids],
                                            np.int64)
            ids = ids_of[id(vids)]
            rs, rd = self.log.at(out["k"])
            snap += reference.multiset_diff(
                reference.edge_keys(ids[out["src"]], ids[out["dst"]], n),
                reference.edge_keys(rs, rd, n))
            if ids.size != n:
                snap += abs(n - ids.size) or 1
                continue
            want = reference.pagerank(rs, rd, n, int(kp["pagerank_iters"]),
                                      float(kp["pagerank_damping"]))
            got = np.empty(n)
            got[ids] = out["pr"]
            err = max(err, float(np.max(np.abs(got - want) / want)))
            lv_want = reference.bfs_levels(rs, rd, n, int(out["root"][1:]))
            lv = out["lv"].astype(np.int64)
            lv_got = np.empty(n, np.int64)
            lv_got[ids] = np.where(lv >= np.iinfo(np.int32).max, -1, lv)
            bfs += int(np.sum(lv_got != lv_want))
        log(f"requests checked {len(self.kept)}: snapshot edges differing "
            f"{snap}, bfs levels differing {bfs}, pagerank max relative "
            f"error {err!r}; requests with a failed churn tx {self.failed}")
        return {"snapshot_edge_diff": {"value": snap, "limit": 0},
                "bfs_level_diff": {"value": bfs, "limit": 0},
                "pagerank_rel_err": {"value": err,
                                     "limit": self.limits["pagerank_rel_err"]},
                "churn_failed": {"value": self.failed, "limit": 0}}


def warm_up(counter, warm: dict, round_: Callable[[], str]) -> None:
    """Warm-up rounds until ``quiet_rounds`` in a row trace no new program
    (at least ``min_rounds``, at most ``max_rounds``): shapes that vary
    with the traffic, such as padded row buckets, are met before the
    window."""
    quiet = 0
    for r in range(int(warm["max_rounds"])):
        c0 = counter.snapshot()
        t0 = time.perf_counter()
        note = round_()
        new = counter.since(c0, counter.snapshot())
        log(f"warm-up round {r}: {time.perf_counter() - t0:.3f} s, {new}, "
            f"{note}")
        quiet = 0 if new["traces"] else quiet + 1
        if r + 1 >= int(warm["min_rounds"]) and \
                quiet >= int(warm["quiet_rounds"]):
            return


def warm_plane_buckets(plane) -> int:
    """Compiles the device column plane's row scatter for every
    power-of-two bucket of rows up to the plane's capacity, and its
    whole-block upload, on each device, on throwaway copies of its
    resident blocks: the buckets a window meets, and whether a compaction
    re-uploads a block, depend on what garbage collection and the churn
    touch, which warm-up requests do not all reach.  Both have the form
    and types of ``DeviceColumnPlane._scatter`` and ``_upload_block``.
    Returns the number of programs run; 0 where the plane holds nothing
    (yet)."""
    import jax
    import jax.numpy as jnp

    blocks = list(getattr(plane, "_dev", None) or [])
    n = 0
    for arr in blocks:
        host = np.zeros(arr.shape[1:], np.int32)
        jax.block_until_ready(arr.at[0].set(jnp.asarray(host)))
        n += 1
        b = 1
        while b <= arr.shape[2]:
            idx = jnp.asarray(np.zeros(b, np.int64))
            rows = jnp.asarray(np.zeros((b, arr.shape[3]), np.int32))
            jax.block_until_ready(arr.at[0, 0, idx, :].set(rows))
            n += 1
            b *= 2
    return n


#: the runners, by the request kinds they serve
LOOPS = {"serve": Serve, "analytics": Analytics}
#: the arrival loops (a mix's ``"loop"``) the runners drive
ARRIVALS = ("closed",)


def loop_of(mix: dict) -> str:
    """The runner for ``mix``; a mix whose request kinds or whose
    ``"loop"`` no runner serves is refused, never run as another."""
    kinds = set(traffic.shares(mix))
    if kinds <= set(reference.READS) | set(reference.WRITES):
        name = "serve"
    elif kinds == {"analytics"}:
        name = "analytics"
    else:
        raise ValueError(f"no loop serves request kinds {sorted(kinds)}")
    if mix.get("loop") not in ARRIVALS:
        raise ValueError(f"no runner drives a {mix.get('loop')!r} loop; "
                         f"known: {list(ARRIVALS)}")
    return name


# ------------------------------------------------------------------ run
def run(cell: Cell, seed: int, seconds: float, traced: bool, t_start: float,
        device: Optional[tuple] = None, scale: Optional[float] = None,
        planted: Optional[Callable] = None) -> dict:
    """One run of ``cell``; returns the result object.  ``device`` is
    (platform, kind, count) from ``device.require_tpu``; ``scale`` shrinks
    the configuration's data for a rehearsal on the CPU; ``planted`` (a
    context manager from ``faults``) breaks the program under the window
    and the check.  ``bench/run.py`` passes neither."""
    import jax

    cache = _persistent_cache()
    log(f"cell {cell.name} seed {seed} seconds {seconds} trace {int(traced)}"
        f"; device {device}; compile cache {cache}")
    counter = compiles.CompileCounter()
    spans = Spans(traced)
    loop = LOOPS[loop_of(cell.mix)](cell, seed, scale, spans)
    loop.setup()
    loop.warmup(counter, cell.mix["warmup"])
    c0 = counter.snapshot()
    n = warm_plane_buckets(loop.w.device_plane)
    log(f"plane scatter and upload programs warmed: {n}, "
        f"{counter.since(c0, counter.snapshot())}")
    spans.reset()

    rec = RunRecord(cell=cell.name, spans=spans,
                    device_kind=(device or (0, jax.devices()[0].device_kind))[1])
    w = loop.w
    plane = w.device_plane
    rec.counters0 = w.counters()
    rec.sim0 = w.sim.now
    rec.plane0 = dict(plane.stats) if plane is not None else {}
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    c0 = counter.snapshot()
    rec.setup_s = time.perf_counter() - t_start
    with contextlib.ExitStack() as stack:
        if planted is not None:
            stack.enter_context(planted())
        try:
            with (btrace.capture(tmp) if traced else nullcontext()):
                with (jax.profiler.TraceAnnotation(btrace.WINDOW) if traced
                      else nullcontext()):
                    loop.window(rec, seconds)
            rec.counters1 = w.counters()
            rec.sim1 = w.sim.now
            rec.plane1 = dict(plane.stats) if plane is not None else {}
            inside = counter.since(c0, counter.snapshot())
            log(f"window: {len(rec.kinds)} requests, "
                f"{rec.done_in_window} completed in the window; programs "
                f"traced/compiled/loaded from cache inside it: {inside}")
            if traced:
                rec.trace = btrace.reduce(btrace.find_xplane(tmp))
        finally:
            if tmp:
                shutil.rmtree(tmp, ignore_errors=True)
        dev = jax.devices()[0]
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
        checks = loop.check(rec)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = m.read(rec)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}
    platform, kind, count = device or (dev.platform, dev.device_kind,
                                       len(jax.devices()))
    out = {"correct": correct, "attempted": len(rec.kinds),
           "failed": int(loop.failed), "metrics": metrics,
           "device": {"platform": platform, "kind": kind, "count": count,
                      "memory_peak_bytes": int(peak)}}
    if traced:
        t = rec.trace
        out["device"]["busy_s"] = t["busy_s"]
        out["device"]["window_s"] = t["window_s"]
        out["breakdown"] = {"device_ops": t["device_ops"][:10],
                            "idle_gaps": t["idle_gaps"][:10]}
    out["checks"] = checks
    return out
