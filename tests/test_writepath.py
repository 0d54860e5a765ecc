"""Group-commit write engine (repro.core.writepath, ISSUE 4).

* randomized batched == per-tx commit equivalence: identical op streams
  through two deployments (``write_group_commit`` on/off), interleaved
  windows, logical aborts — final committed state and read results must
  match;
* reads at stamps straddling batch boundaries: a stamp captured between
  windows must return bit-identical results via frontier, scalar, and
  analytics snapshot AFTER later batches commit (later windows
  invisible at the earlier stamp);
* ``LastUpdateTable`` vs the per-vertex dict walk (property test) and
  the vectorized batch classifier vs ``clock.compare``;
* the duplicate-stamp ``order_events`` regression (benchmarks/
  coordination ``CycleError``);
* shard plan LRU: mutually concurrent query stamps keep separate plans
  (no thrash), budget evictions counted;
* scalar delivery coalescing at the shard (same-(prog, stamp) entry
  lists merge into one ``run_entries_scalar`` execution).
"""

import numpy as np
import pytest

from repro.core import Weaver, WeaverConfig
from repro.core import analytics as A
from repro.core import frontier as F
from repro.core.analytics import SnapshotEngine
from repro.core.clock import Order, Stamp, compare
from repro.core.oracle import TimelineOracle
from repro.core.simulation import Simulator
from repro.core.store import BackingStore
from repro.core.writepath import (OK, RETRY, LastUpdateTable,
                                  classify_write_sets)


def make_weaver(seed=0, n_shards=4, n_gk=2, **kw):
    return Weaver(WeaverConfig(n_gatekeepers=n_gk, n_shards=n_shards,
                               gc_period=0, seed=seed, **kw))


class _Stamps:
    """Totally-ordered synthetic stamps (round-robin gatekeepers)."""

    def __init__(self, n_gk):
        self.n_gk = n_gk
        self.clock = [0] * n_gk
        self.i = 0

    def next(self):
        g = self.i % self.n_gk
        self.i += 1
        self.clock[g] += 1
        return Stamp(0, tuple(self.clock), g, self.clock[g])

    def query(self):
        g = self.i % self.n_gk
        self.i += 1
        self.clock = [c + 1 for c in self.clock]
        return Stamp(0, tuple(self.clock), g, self.clock[g])


# ---------------------------------------------------------------------------
# oracle regression
# ---------------------------------------------------------------------------

class TestOracleDuplicates:
    def test_duplicate_stamps_with_constraints(self):
        """order_events used to raise a spurious CycleError when the
        request repeated a stamp that had pending predecessors (Kahn
        counted duplicates, the ready set deduped) — the
        benchmarks/coordination tau-sweep failure."""
        o = TimelineOracle()
        a = Stamp(0, (1, 0), 0, 1)
        b = Stamp(0, (0, 1), 1, 1)
        first = o.order_events([a, b])       # commits one direction
        again = o.order_events([b, a, b, b, a])
        assert again == first and len(again) == 2

    def test_many_duplicates_random(self):
        rng = np.random.default_rng(0)
        o = TimelineOracle()
        pool = [Stamp(0, (int(rng.integers(1, 6)), int(rng.integers(1, 6))),
                      int(rng.integers(0, 2)), i) for i in range(12)]
        for _ in range(50):
            req = [pool[int(rng.integers(len(pool)))]
                   for _ in range(int(rng.integers(2, 9)))]
            chain = o.order_events(req)      # must never cycle
            assert len(chain) == len({s.key() for s in req})


# ---------------------------------------------------------------------------
# LastUpdateTable / classifier
# ---------------------------------------------------------------------------

class TestLastUpdateTable:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_matches_dict_walk(self, seed):
        """The packed table must agree with StoredVertex.last_update
        after a random stream of per-tx and batched commits, including
        aborted transactions (no table side effects)."""
        rng = np.random.default_rng(seed)
        sim = Simulator(seed=seed)
        store = BackingStore(sim, 4)
        sg = _Stamps(2)
        vids = [f"v{i}" for i in range(30)]
        live = set()
        for r in range(60):
            ops = []
            for _ in range(int(rng.integers(1, 6))):
                c = rng.random()
                v = vids[int(rng.integers(len(vids)))]
                if c < 0.35:
                    ops.append({"op": "create_vertex", "vid": v})
                elif c < 0.6 and live:
                    s = str(rng.choice(sorted(live)))
                    d = vids[int(rng.integers(len(vids)))]
                    ops.append({"op": "create_edge", "src": s, "dst": d})
                elif c < 0.8 and live:
                    s = str(rng.choice(sorted(live)))
                    ops.append({"op": "set_vertex_prop", "vid": s,
                                "key": "k", "value": int(rng.integers(9))})
                elif live:
                    s = str(rng.choice(sorted(live)))
                    ops.append({"op": "delete_vertex", "vid": s})
            if not ops:
                continue
            if rng.random() < 0.5:
                try:
                    store.apply(ops, sg.next())
                except ValueError:
                    pass
                else:
                    self._track(ops, live)
            else:
                # batch of 1-3 (the remaining ops split arbitrarily)
                cut = sorted(rng.choice(max(len(ops), 1),
                                        size=min(2, len(ops)),
                                        replace=False).tolist())
                parts, prev = [], 0
                for c in cut + [len(ops)]:
                    if ops[prev:c]:
                        parts.append(ops[prev:c])
                    prev = c
                res = store.apply_batch([(p, sg.next(), None) for p in parts])
                for (ok, _, _), p in zip(res, parts):
                    if ok:
                        self._track(p, live)
            # invariant: table == dict walk, for every vid ever seen
            for v in vids:
                assert store.last_updates.get(v) == store.last_update_of(v)

    @staticmethod
    def _track(ops, live):
        for op in ops:
            if op["op"] == "create_vertex":
                live.add(op["vid"])
            elif op["op"] == "delete_vertex":
                live.discard(op["vid"])

    @pytest.mark.parametrize("force_kernel", [None, True])
    def test_classifier_matches_compare(self, force_kernel, monkeypatch):
        """classify_write_sets must reproduce clock.compare semantics
        per (tx, vid) row: AFTER -> retry, CONCURRENT -> refine residue,
        BEFORE/absent -> pass — on the host path and on the device
        path's padded launch alike."""
        monkeypatch.setattr(A, "FORCE_KERNEL", force_kernel)
        rng = np.random.default_rng(3)
        table = LastUpdateTable()
        stamps = {}
        for i in range(40):
            s = Stamp(0, (int(rng.integers(0, 6)), int(rng.integers(0, 6))),
                      int(rng.integers(0, 2)), i + 1)
            vid = f"v{i}"
            table.record([vid], s)
            stamps[vid] = s
        for _ in range(200):
            tx = Stamp(0, (int(rng.integers(0, 6)), int(rng.integers(0, 6))),
                       int(rng.integers(0, 2)), 1000)
            ws = [f"v{int(rng.integers(0, 50))}"      # incl. absent vids
                  for _ in range(int(rng.integers(1, 5)))]
            (verdict,), rows = classify_write_sets(table, [ws], [tx])
            assert rows == len(ws)
            want_retry, want_conc = False, []
            for v in ws:
                upd = stamps.get(v)
                if upd is None:
                    continue
                o = compare(upd, tx)
                if o is Order.AFTER:
                    want_retry = True
                elif o is Order.CONCURRENT:
                    want_conc.append(upd)
            if want_retry:
                assert verdict.status == RETRY
            else:
                assert verdict.status == OK
                assert verdict.concurrent == want_conc


# ---------------------------------------------------------------------------
# batched == per-tx equivalence
# ---------------------------------------------------------------------------

def _fingerprint(w):
    """Mode-invariant committed state (eids/stamps legitimately differ
    between modes: multisets of live edges + property versions)."""
    out = {}
    for vid, v in w.store.vertices.items():
        alive = v.delete_ts is None
        edges = sorted(dst for dst, _, dts in v.edges.values()
                       if dts is None) if alive else []
        props = sorted((k, val) for k, vs in v.props.items()
                       for val, _ in vs)
        out[vid] = (alive, edges, props)
    return out


def _gen_wave(rng, vids, known, wave_i):
    """One wave of tx specs (identical across modes)."""
    wave = []
    for _ in range(int(rng.integers(6, 14))):
        c = rng.random()
        v = vids[int(rng.integers(len(vids)))]
        if c < 0.25:
            wave.append(("create", v))           # may abort: exists
        elif c < 0.65 and known:
            u = str(rng.choice(sorted(known)))
            wave.append(("edge", u, v))          # may abort: src dead
        elif c < 0.8 and known:
            u = str(rng.choice(sorted(known)))
            wave.append(("prop", u, float(wave_i)))
        elif known:
            u = str(rng.choice(sorted(known)))
            wave.append(("delete", u))           # may abort: already dead
    return wave


def _submit_wave(w, wave, results, gatekeeper=None):
    for i, spec in enumerate(wave):
        tx = w.begin_tx()
        if spec[0] == "create":
            tx.create_vertex(spec[1])
        elif spec[0] == "edge":
            tx.create_edge(spec[1], spec[2])
        elif spec[0] == "prop":
            tx.set_vertex_prop(spec[1], "score", spec[2])
        else:
            tx.delete_vertex(spec[1])
        g = (i % len(w.gatekeepers)) if gatekeeper is None else gatekeeper
        w.submit_tx(tx, results.append, gatekeeper=g)


class TestGroupCommitEquivalence:
    @pytest.mark.parametrize("seed", [0, 1, 7])
    def test_batched_equals_per_tx_single_gk(self, seed):
        """Identical randomized op streams — including conflicting
        creates/deletes and logical aborts — through both modes on ONE
        gatekeeper (admission order pins the serial order in both, so
        per-tx commit/abort outcomes must match exactly), with reads
        between interleaved windows."""
        modes = {}
        for window in (0.0, 0.25e-3):
            rng = np.random.default_rng(seed)
            w = make_weaver(seed=seed, write_group_commit=window,
                            write_group_max=8)
            vids = [f"n{i}" for i in range(24)]
            known = set()
            reads, outcomes = [], []
            for wave_i in range(8):
                wave = _gen_wave(rng, vids, known, wave_i)
                results = []
                _submit_wave(w, wave, results, gatekeeper=0)
                w.settle(30e-3)          # quiesce: interleaved windows done
                assert len(results) == len(wave)
                outcomes.append([r.ok for r in results])
                for spec, r in zip(wave, results):
                    if r.ok:
                        if spec[0] == "create":
                            known.add(spec[1])
                        elif spec[0] == "delete":
                            known.discard(spec[1])
                if known:
                    root = sorted(known)[0]
                    trav, _, _ = w.run_program(
                        "traverse", [(root, {"depth": 2})])
                    cnt, _, _ = w.run_program("count_edges", [(root, None)])
                    reads.append((sorted(trav), cnt))
            modes[window] = (outcomes, reads, _fingerprint(w), w.counters())
        (o1, r1, f1, c1), (o2, r2, f2, c2) = modes[0.0], modes[0.25e-3]
        assert o1 == o2, "commit/abort outcomes diverged"
        assert r1 == r2, "quiescent read results diverged"
        assert f1 == f2, "final committed state diverged"
        assert c2["tx_batches"] > 0
        assert c2["tx_batch_size_sum"] >= c2["tx_batches"]
        assert c1["tx_batches"] == 0

    @pytest.mark.parametrize("seed", [2, 11])
    def test_batched_equals_per_tx_cross_gk(self, seed):
        """Cross-gatekeeper concurrency (the refinement residue): a
        conflict-free write mix — edges and same-vertex property writes
        from BOTH gatekeepers — must commit fully in both modes and
        converge to the same state.  (Which of two cross-gk logical
        conflicts wins is timing-dependent in BOTH modes, so the strict
        outcome comparison lives in the single-gk test.)"""
        modes = {}
        for window in (0.0, 0.25e-3):
            rng = np.random.default_rng(seed)
            w = make_weaver(seed=seed, write_group_commit=window,
                            write_group_max=8)
            vids = [f"n{i}" for i in range(20)]
            tx = w.begin_tx()
            for v in vids:
                tx.create_vertex(v)
            assert w.run_tx(tx).ok
            reads = []
            for wave_i in range(6):
                results = []
                wave = []
                for _ in range(12):
                    u = vids[int(rng.integers(len(vids)))]
                    v = vids[int(rng.integers(len(vids)))]
                    if rng.random() < 0.6:
                        wave.append(("edge", u, v))
                    else:
                        wave.append(("prop", u, float(wave_i)))
                _submit_wave(w, wave, results)   # round-robin both gks
                w.settle(30e-3)
                assert len(results) == len(wave)
                assert all(r.ok for r in results)
                trav, _, _ = w.run_program("traverse",
                                           [(vids[0], {"depth": 2})])
                reads.append(sorted(trav))
            modes[window] = (reads, _fingerprint(w), w.counters())
        (r1, f1, c1), (r2, f2, c2) = modes[0.0], modes[0.25e-3]
        assert r1 == r2, "quiescent read results diverged"
        assert f1 == f2, "final committed state diverged"
        assert c2["tx_batches"] > 0
        assert c2["conflict_rows_checked"] > 0

    def test_reads_straddle_batch_boundaries(self):
        """A stamp captured between windows must read bit-identically
        via frontier, scalar, and analytics AFTER later windows commit
        (later batches invisible at the earlier stamp)."""
        w = make_weaver(seed=3, write_group_commit=0.25e-3,
                        write_group_max=8)
        vids = [f"s{i}" for i in range(12)]
        tx = w.begin_tx()
        for v in vids:
            tx.create_vertex(v)
        assert w.run_tx(tx).ok
        results = []
        for i in range(10):
            tx = w.begin_tx()
            tx.create_edge(vids[i % 12], vids[(i + 1) % 12])
            w.submit_tx(tx, results.append)
        w.settle(30e-3)
        assert all(r.ok for r in results)
        # stamp between windows: issued now, before the next wave
        at = w.gatekeepers[0]._tick()
        ga_before = SnapshotEngine(w).snapshot(at)
        r_before, _ = F.run_local(w, "traverse", [(vids[0], {"depth": 0})],
                                  at, use_frontier=True)
        # ---- later windows commit ----
        results2 = []
        for i in range(14):
            tx = w.begin_tx()
            tx.create_edge(vids[(i + 5) % 12], vids[(i + 9) % 12])
            w.submit_tx(tx, results2.append)
        w.settle(30e-3)
        assert all(r.ok for r in results2)
        # identical reads at `at` across all three paths, post-commit
        r_f, _ = F.run_local(w, "traverse", [(vids[0], {"depth": 0})], at,
                             use_frontier=True)
        r_s, _ = F.run_local(w, "traverse", [(vids[0], {"depth": 0})], at,
                             use_frontier=False)
        ga = SnapshotEngine(w).snapshot(at)
        lv = np.asarray(A.bfs_levels_ga(ga, [ga.index[vids[0]]]))
        r_a = sorted(ga.vids[i] for i in np.nonzero(lv < A.INF)[0])
        assert r_f == r_s == r_a == r_before
        assert int(ga.edge_src.size) == int(ga_before.edge_src.size), \
            "later batches leaked into the earlier stamp"

    def test_logical_abort_is_per_tx_within_batch(self):
        """One bad tx aborts alone; the rest of its window commits."""
        w = make_weaver(seed=4, write_group_commit=0.5e-3,
                        write_group_max=16)
        tx = w.begin_tx()
        tx.create_vertex("a")
        assert w.run_tx(tx).ok
        results = []
        specs = [("create", "b"), ("create", "a"),    # dup -> abort
                 ("edge", "a", "b"), ("create", "c")]
        for spec in specs:
            tx = w.begin_tx()
            if spec[0] == "create":
                tx.create_vertex(spec[1])
            else:
                tx.create_edge(spec[1], spec[2])
            w.submit_tx(tx, results.append, gatekeeper=0)  # one window
        w.settle(30e-3)
        assert [r.ok for r in results] == [True, False, True, True]
        assert "exists" in results[1].error
        c = w.counters()
        assert c["tx_aborted"] == 1
        assert c["tx_batches"] >= 1

    def test_stale_window_timer_does_not_shorten_next_window(self):
        """A timer armed for a window that a max-count trigger already
        flushed must not fire into the NEXT window (it would cut every
        later window short under load)."""
        w = make_weaver(seed=8, write_group_commit=10e-3,
                        write_group_max=4)
        tx = w.begin_tx()
        tx.create_vertex("r")
        assert w.run_tx(tx).ok          # own (max-1-sized) windows
        base = w.counters()["tx_batches"]
        results = []
        for i in range(4):              # fills group_max -> instant flush
            tx = w.begin_tx()
            tx.set_vertex_prop("r", "a", i)
            w.submit_tx(tx, results.append, gatekeeper=0)
        w.settle(2e-3)
        assert w.counters()["tx_batches"] == base + 1
        for i in range(2):              # new window, deadline now+10ms
            tx = w.begin_tx()
            tx.set_vertex_prop("r", "b", i)
            w.submit_tx(tx, results.append, gatekeeper=0)
        w.settle(7e-3)                  # ~9ms: stale timer would have fired
        assert w.counters()["tx_batches"] == base + 1, \
            "second window flushed early (stale timer)"
        w.settle(8e-3)                  # past the real ~12ms deadline
        assert w.counters()["tx_batches"] == base + 2
        assert len(results) == 6 and all(r.ok for r in results)

    def test_batch_prefix_stops_at_pending_program_stamp(self):
        """A WriteBatch item merely CONCURRENT with a gated program's
        stamp must not apply inside the bulk prefix: per-tx execution
        re-checks runnable programs between items, and the item may yet
        be oracle-ordered after the program (a re-create would destroy
        history the program still needs).  The prefix stops; the
        remainder is requeued as the new head."""
        from repro.core.writepath import WriteBatch
        w = make_weaver(seed=10, n_shards=1)
        sh = w.shards[0]
        sh.partition.create_vertex("v", Stamp(0, (1, 0), 0, 1))
        sh.partition.set_vertex_prop("v", "k", "old", Stamp(0, (2, 0), 0, 2))
        # gated program, concurrent with the batch's 2nd/3rd items
        p_stamp = Stamp(0, (3, 5), 1, 5)

        class _Coord:
            def report(self, *a, **k):
                pass
        sh.deliver_prog(1, ("t", 1), "get_node", p_stamp, [("v", None)],
                        _Coord())
        wb = WriteBatch([
            (Stamp(0, (3, 0), 0, 3),
             [{"op": "set_vertex_prop", "vid": "v", "key": "k",
               "value": "mid"}]),
            (Stamp(0, (4, 0), 0, 4), [{"op": "delete_vertex", "vid": "v"}]),
            (Stamp(0, (5, 0), 0, 5), [{"op": "create_vertex", "vid": "v"}]),
        ])
        sh.enqueue(0, 1, wb.stamp, "txbatch", wb)
        # other queue: dominating NOP head (would allow the WHOLE batch
        # if only queue heads bounded the prefix)
        sh.enqueue(1, 1, Stamp(0, (9, 9), 1, 9), "nop", None)
        head = sh.queues[0][0]
        assert head.kind == "txbatch" and len(head.payload) == 2, \
            "prefix overtook a concurrent pending program"
        assert head.stamp.key() == (0, (4, 0), 0)
        v = sh.partition.vertices["v"]
        assert v.delete_ts is None, "delete applied ahead of the program"
        assert len(v.props["k"]) == 2, "history lost ahead of the program"

    def test_retry_abort_after_max(self):
        """_retry_or_abort gives up after MAX_RETRIES (shared bound of
        the per-tx and group paths)."""
        from repro.core.gatekeeper import MAX_RETRIES
        w = make_weaver(seed=5, write_group_commit=0.5e-3)
        gk = w.gatekeepers[0]
        got = []
        stamp = gk._tick()
        gk._retry_or_abort((None, [], stamp,
                            lambda ok, err, s: got.append((ok, err)),
                            MAX_RETRIES, 0.0, None))
        w.settle(5e-3)
        assert got == [(False, "too many retries")]
        assert w.counters()["tx_aborted"] == 1
        assert w.counters()["tx_retried"] == 1


# ---------------------------------------------------------------------------
# bulk column appends
# ---------------------------------------------------------------------------

class TestBulkColumns:
    @pytest.mark.parametrize("seed", [0, 4])
    def test_apply_batch_columns_equal_per_op(self, seed):
        """MVGraphPartition.apply_batch (buffered column appends, one
        extend per table) must leave byte-identical columns to per-op
        application of the same (stamp, op) stream."""
        from repro.core.mvgraph import MVGraphPartition

        def gen(rng, n_rounds=25):
            vids, eids, rounds, nxt = [], {}, [], [0]
            for r in range(n_rounds):
                ops = []
                for _ in range(int(rng.integers(1, 10))):
                    c = rng.random()
                    if c < 0.3 or not vids:
                        vid = f"v{len(vids)}"
                        vids.append(vid)
                        ops.append({"op": "create_vertex", "vid": vid})
                    elif c < 0.55 and len(vids) >= 2:
                        s, d = rng.choice(len(vids), 2)
                        nxt[0] += 1
                        eids.setdefault(f"v{s}", []).append(nxt[0])
                        ops.append({"op": "create_edge", "src": f"v{s}",
                                    "dst": f"v{d}", "eid": nxt[0]})
                    elif c < 0.7:
                        s = rng.integers(len(vids))
                        es = eids.get(f"v{s}")
                        if es:
                            ops.append({"op": "delete_edge",
                                        "src": f"v{s}", "eid": es.pop()})
                    elif c < 0.9:
                        s = rng.integers(len(vids))
                        ops.append({"op": "set_vertex_prop",
                                    "vid": f"v{s}", "key": "k",
                                    "value": float(rng.random())})
                    else:
                        s = rng.integers(len(vids))
                        es = eids.get(f"v{s}")
                        if es:
                            ops.append({"op": "set_edge_prop",
                                        "src": f"v{s}", "eid": es[-1],
                                        "key": "w", "value": 1.0})
                rounds.append(ops)
            return rounds

        def run(mode):
            p = MVGraphPartition(3)
            sg = _Stamps(3)
            for ops in gen(np.random.default_rng(seed)):
                items = [(sg.next(), [op]) for op in ops]
                if mode == "batch":
                    p.apply_batch(items)
                else:
                    for ts, opl in items:
                        for op in opl:
                            p.apply_op(op, ts)
            return p.columns

        ca, cb = run("per-op"), run("batch")
        for name in ("v_gid", "v_create", "v_delete", "e_src", "e_dst",
                     "e_create", "e_delete"):
            assert np.array_equal(getattr(ca, name).view(),
                                  getattr(cb, name).view()), name
        assert sorted(ca.v_patch) == sorted(cb.v_patch)
        assert sorted(ca.e_patch) == sorted(cb.e_patch)
        assert ca.v_slot == cb.v_slot and ca.e_slot == cb.e_slot
        for t in ("v_props", "e_props"):
            pa, pb = getattr(ca, t), getattr(cb, t)
            for name in ("owner", "key", "val", "num", "stamp"):
                assert np.array_equal(getattr(pa, name).view(),
                                      getattr(pb, name).view()), (t, name)
            assert sorted(pa.patch) == sorted(pb.patch)
            assert pa.by_owner == pb.by_owner


# ---------------------------------------------------------------------------
# plan LRU
# ---------------------------------------------------------------------------

class TestPlanLRU:
    def _loaded_shard(self, entries=4):
        w = make_weaver(seed=6, n_shards=1, plan_cache_entries=entries)
        sh = w.shards[0]
        sg = _Stamps(2)
        for i in range(6):
            sh.partition.create_vertex(f"p{i}", sg.next())
        for i in range(5):
            sh.partition.create_edge(f"p{i}", f"p{i+1}", sg.next())
        return w, sh, sg

    def test_concurrent_stamps_keep_separate_plans(self):
        w, sh, sg = self._loaded_shard()
        base = list(sg.clock)
        sa = Stamp(0, (base[0] + 5, base[1] + 1), 0, base[0] + 5)
        sb = Stamp(0, (base[0] + 1, base[1] + 5), 1, base[1] + 5)
        assert compare(sa, sb) is Order.CONCURRENT
        pa = sh._frontier_plan(sa)
        pb = sh._frontier_plan(sb)
        assert pa is not pb
        c0 = w.sim.counters.plan_cold_builds
        # alternating queries now hit their own cached plans: no thrash
        assert sh._frontier_plan(sa) is pa
        assert sh._frontier_plan(sb) is pb
        assert sh._frontier_plan(sa) is pa
        assert w.sim.counters.plan_cold_builds == c0
        assert w.sim.counters.plan_cache_evictions == 0

    def test_budget_evicts_lru(self):
        w, sh, sg = self._loaded_shard(entries=1)
        base = list(sg.clock)
        sa = Stamp(0, (base[0] + 5, base[1] + 1), 0, base[0] + 5)
        sb = Stamp(0, (base[0] + 1, base[1] + 5), 1, base[1] + 5)
        pa = sh._frontier_plan(sa)
        sh._frontier_plan(sb)                  # evicts pa (budget 1)
        assert w.sim.counters.plan_cache_evictions == 1
        assert sh._frontier_plan(sa) is not pa  # cold again: thrash mode
        assert w.sim.counters.plan_cold_builds >= 3

    def test_dominating_stamp_still_reuses(self):
        """The PR 3 settled-reuse contract survives the LRU."""
        w, sh, sg = self._loaded_shard()
        s1 = sg.query()
        p1 = sh._frontier_plan(s1)
        assert p1.settled
        assert sh._frontier_plan(sg.query()) is p1


# ---------------------------------------------------------------------------
# scalar delivery coalescing
# ---------------------------------------------------------------------------

class TestScalarCoalescing:
    @staticmethod
    def _build(coalesce):
        w = Weaver(WeaverConfig(n_gatekeepers=2, n_shards=4, gc_period=0,
                                seed=9, frontier_coalesce=coalesce))
        vids = [f"c{i}" for i in range(16)]
        tx = w.begin_tx()
        for v in vids:
            tx.create_vertex(v)
        assert w.run_tx(tx).ok
        eids = {}
        tx = w.begin_tx()
        for i in range(16):
            for j in (1, 2, 3):
                eids[(i, j)] = tx.create_edge(vids[i], vids[(i + j) % 16])
        assert w.run_tx(tx).ok
        # unhashable edge-filter constant -> scalar path with emits
        tx = w.begin_tx()
        for handle in eids.values():
            tx.set_edge_prop(handle, "tag", [1])
        assert w.run_tx(tx).ok
        return w, vids

    def test_merges_and_matches_uncoalesced(self):
        results = {}
        for coalesce in (True, False):
            w, vids = self._build(coalesce)
            params = {"depth": 0, "edge_property": ("tag", [1])}
            res, _, _ = w.run_program("traverse", [(vids[0], params)])
            c = w.counters()
            assert c["frontier_batches"] == 0, "filter should force scalar"
            results[coalesce] = (sorted(res), c["scalar_coalesced"])
        assert results[True][0] == results[False][0]
        assert results[True][1] > 0, "no scalar deliveries merged"
        assert results[False][1] == 0


# ---------------------------------------------------------------------------
# shard reorder buffer (cross-gatekeeper batch merging)
# ---------------------------------------------------------------------------

class TestReorderBuffer:
    def test_merges_foreign_runnable_prefix(self):
        """Synthetic interleaving a1 ≺ b1 ≺ a2 ≺ b2 ≺ a3 across two
        gatekeeper batches: executing gk0's batch must pull b1/b2 into
        the same bulk apply, stop at a3 (gk1's stream exhausts with no
        next-item bound), and requeue the leftover."""
        from repro.core.writepath import WriteBatch

        w = make_weaver(seed=0, n_gk=2, n_shards=1)
        sh = w.shards[0]
        a = [Stamp(0, (1, 0), 0, 1), Stamp(0, (2, 1), 0, 2),
             Stamp(0, (3, 2), 0, 3)]
        b = [Stamp(0, (1, 1), 1, 1), Stamp(0, (2, 2), 1, 2)]
        mk = lambda i: [{"op": "create_vertex", "vid": f"x{i}"}]
        sh.enqueue(0, 1, a[0], "txbatch",
                   WriteBatch([(a[0], mk(0)), (a[1], mk(1)),
                               (a[2], mk(2))]))
        # gk1 queue was empty -> nothing ran yet
        assert w.counters()["crossgk_batch_merges"] == 0
        sh.enqueue(1, 1, b[0], "txbatch",
                   WriteBatch([(b[0], mk(3)), (b[1], mk(4))]))
        c = w.counters()
        assert c["crossgk_batch_merges"] == 1
        assert c["crossgk_merged_txs"] == 2
        # a1, b1, a2, b2 applied; a3 requeued as the gk0 leftover
        assert set(sh.partition.vertices) == {"x0", "x1", "x3", "x4"}
        assert len(sh.queues[0]) == 1
        assert sh.queues[0][0].kind == "txbatch"
        assert [s for s, _ in sh.queues[0][0].payload.items] == [a[2]]
        assert not sh.queues[1]
        # the merged items acked to their ORIGIN gatekeepers' stamps
        for s in (a[0], a[1], b[0], b[1]):
            assert s.key() in sh._applied

    def test_concurrent_foreign_head_not_merged(self):
        """A foreign batch whose head is vector-concurrent with the
        executing batch's items must NOT be pulled in (ordering it
        would need the oracle — the buffer is refinement-free)."""
        from repro.core.writepath import WriteBatch

        w = make_weaver(seed=0, n_gk=2, n_shards=1)
        sh = w.shards[0]
        a = [Stamp(0, (1, 0), 0, 1), Stamp(0, (2, 0), 0, 2)]
        b = [Stamp(0, (0, 1), 1, 1)]          # concurrent with both
        mk = lambda i: [{"op": "create_vertex", "vid": f"y{i}"}]
        sh.enqueue(0, 1, a[0], "txbatch",
                   WriteBatch([(a[0], mk(0)), (a[1], mk(1))]))
        sh.enqueue(1, 1, b[0], "txbatch", WriteBatch([(b[0], mk(2))]))
        c = w.counters()
        assert c["crossgk_batch_merges"] == 0
        assert c["crossgk_merged_txs"] == 0

    def test_end_to_end_merge_and_state_equivalence(self):
        """Staggered interleaved cross-gk submissions with fast
        vector-clock announcements: merges fire in the real pipeline and
        the final state matches the per-tx (window=0) oracle.  One shard
        so timing is independent of the per-process vid hash seed."""
        modes = {}
        for window in (0.0, 0.3e-3):
            rng = np.random.default_rng(0)
            w = make_weaver(seed=2, n_shards=1, write_group_commit=window,
                            write_group_max=32, tau=0.05e-3,
                            tau_nop=0.05e-3)
            vids = [f"n{i}" for i in range(20)]
            tx = w.begin_tx()
            for v in vids:
                tx.create_vertex(v)
            assert w.run_tx(tx).ok
            w.settle(5e-3)
            res = []

            def submit(g):
                tx = w.begin_tx()
                u = vids[int(rng.integers(len(vids)))]
                v = vids[int(rng.integers(len(vids)))]
                tx.create_edge(u, v)
                w.submit_tx(tx, res.append, gatekeeper=g)

            t = 0.0
            for i in range(80):
                g = i % 2
                # gk1's arrivals lag half a window so its flushed batch
                # interleaves (vector-ordered) with gk0's next batch
                w.sim.schedule(t + (0.15e-3 if g else 0.0), submit, g)
                t += 0.15e-3
            w.settle(100e-3)
            assert len(res) == 80 and all(r.ok for r in res)
            modes[window] = (_fingerprint(w), w.counters())
        (f1, c1), (f2, c2) = modes[0.0], modes[0.3e-3]
        assert f1 == f2, "final committed state diverged"
        assert c2["crossgk_batch_merges"] >= 1
        assert c2["crossgk_merged_txs"] >= c2["crossgk_batch_merges"]
        assert c1["crossgk_batch_merges"] == 0   # no batches, no merges
