"""Frontier-batched node-program runtime: randomized equivalence with the
per-vertex path at identical stamps (under churn, GC, property writes),
message/entry accounting, property-column exposure, and the sorted
segment-op helpers.  Seeded-random, tier-1."""

import numpy as np
import pytest

from repro.core import Weaver, WeaverConfig
from repro.core import analytics as A
from repro.core import frontier as F
from repro.core.analytics import SnapshotEngine
from repro.core.clock import Stamp
from repro.core.nodeprog import REGISTRY


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


def make_weaver(seed=0, n_shards=3):
    return Weaver(WeaverConfig(n_gatekeepers=2, n_shards=n_shards,
                               gc_period=0, seed=seed))


def mutate(rng, w, sg, live, edges, round_i, props=True, deletes=True):
    part = lambda v: w.shards[w.store.place(v)].partition
    for _ in range(int(rng.integers(5, 30))):
        op = rng.integers(0, 100)
        if op < 25 or not live:                       # create vertex
            vid = f"v{round_i}_{rng.integers(0, 1 << 30)}"
            if vid in live:
                continue
            part(vid).create_vertex(vid, sg.next())
            live.add(vid)
        elif op < 55:                                 # create edge
            s = str(rng.choice(sorted(live)))
            d = str(rng.choice(sorted(live)))
            e = part(s).create_edge(s, d, sg.next())
            edges.append((s, e.eid))
            if props and rng.random() < 0.6:
                part(s).set_edge_prop(s, e.eid, "rel",
                                      str(rng.choice(["F", "G"])),
                                      sg.next())
            if props and rng.random() < 0.6:
                part(s).set_edge_prop(s, e.eid, "weight",
                                      float(rng.integers(1, 6)), sg.next())
        elif op < 70 and edges:                       # delete edge
            s, eid = edges[int(rng.integers(0, len(edges)))]
            if s not in live:
                continue
            e = part(s).vertices[s].out_edges.get(eid)
            if e is not None and e.delete_ts is None:
                part(s).delete_edge(s, eid, sg.next())
        elif op < 80 and props and live:              # vertex prop
            vid = str(rng.choice(sorted(live)))
            part(vid).set_vertex_prop(vid, "value",
                                      int(rng.integers(0, 9)), sg.next())
        elif op < 88 and deletes and len(live) > 2:   # delete vertex
            vid = str(rng.choice(sorted(live)))
            part(vid).delete_vertex(vid, sg.next())
            live.discard(vid)
        elif props and edges:                         # re-set edge prop
            s, eid = edges[int(rng.integers(0, len(edges)))]
            if s in live and eid in part(s).vertices[s].out_edges:
                part(s).set_edge_prop(s, eid, "weight",
                                      float(rng.integers(1, 6)), sg.next())


class TestFrontierEquivalence:
    """Frontier path == per-vertex path at identical stamps."""

    def _both(self, w, name, entries, at):
        place = lambda vid: w.store.place(vid)
        r_f, s_f = F.run_local(w, name, entries, at, use_frontier=True,
                               shard_of=place)
        r_s, s_s = F.run_local(w, name, entries, at, use_frontier=False,
                               shard_of=place)
        return r_f, r_s, s_f, s_s

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_random_churn(self, seed):
        rng = np.random.default_rng(seed)
        w = make_weaver(seed)
        sg = _Stamps(2)
        live, edges = set(), []
        for round_i in range(8):
            mutate(rng, w, sg, live, edges, round_i)
            if round_i % 3 == 2:   # interleave GC (may purge + compact)
                horizon = Stamp(0, tuple(sg.clock), -1, 0)
                for sh in w.shards:
                    sh.partition.collect(horizon)
            at = sg.query()
            pool = sorted(live)
            src = str(rng.choice(pool))
            tgt = str(rng.choice(pool))
            cases = [
                ("get_node", [(src, None)]),
                ("count_edges", [(src, None)]),
                ("traverse", [(src, {"depth": 0})]),
                ("traverse", [(src, {"depth": 0, "max_depth": 2})]),
                ("traverse", [(src, {"depth": 0,
                                     "edge_property": ("rel", "F")})]),
                ("reachable", [(src, {"target": tgt})]),
                ("sssp", [(src, {"target": tgt, "max_depth": 128})]),
            ]
            for name, entries in cases:
                r_f, r_s, _, _ = self._both(w, name, entries, at)
                assert r_f == r_s, (name, at, r_f, r_s)

    def test_sssp_fractional_weights_exact(self):
        """Weighted sssp stays exact in float64 on the frontier path with
        the kernel forced: 0.1 + 0.2 + 0.3 and 2^24 + 1 both round in
        float32."""
        w = make_weaver(7)
        sg = _Stamps(2)
        part = lambda v: w.shards[w.store.place(v)].partition
        for v in ("a", "b", "c", "t", "x", "y"):
            part(v).create_vertex(v, sg.next())
        for s, d, wt in (("a", "b", 0.1), ("b", "c", 0.2), ("c", "t", 0.3),
                         ("a", "t", 0.6000000000000002),
                         ("a", "x", 16777216.0), ("x", "y", 1.0)):
            e = part(s).create_edge(s, d, sg.next())
            part(s).set_edge_prop(s, e.eid, "weight", wt, sg.next())
        at = sg.query()
        old = A.FORCE_KERNEL
        A.FORCE_KERNEL = True
        try:
            for tgt, want in (("t", 0.1 + 0.2 + 0.3), ("y", 16777217.0)):
                entries = [("a", {"target": tgt, "max_depth": 8})]
                r_f, r_s, _, _ = self._both(w, "sssp", entries, at)
                assert r_f == r_s == want, (tgt, r_f, r_s)
        finally:
            A.FORCE_KERNEL = old

    def test_matches_analytics_reference(self):
        """traverse == BFS-reachable set on the engine snapshot;
        count_edges == snapshot out-degree (three-way agreement)."""
        rng = np.random.default_rng(5)
        w = make_weaver(5)
        sg = _Stamps(2)
        live, edges = set(), []
        mutate(rng, w, sg, live, edges, 0, props=False, deletes=False)
        mutate(rng, w, sg, live, edges, 1, props=False, deletes=False)
        at = sg.query()
        ga = SnapshotEngine(w).snapshot(at)
        src = sorted(live)[0]
        r_f, r_s, _, _ = self._both(w, "traverse", [(src, {"depth": 0})], at)
        lv = np.asarray(A.bfs_levels_ga(ga, [ga.index[src]]))
        want = sorted(ga.vids[i] for i in np.nonzero(lv < A.INF)[0])
        assert r_f == r_s == want
        deg = np.bincount(ga.edge_src, minlength=ga.n_nodes)
        for vid in sorted(live)[:5]:
            c_f, c_s, _, _ = self._both(w, "count_edges", [(vid, None)], at)
            assert c_f == c_s == int(deg[ga.index[vid]])

    def test_block_render_multiset(self):
        """block_render (order-insensitive: reduce is the raw list)."""
        w = make_weaver(3)
        sg = _Stamps(2)
        part = lambda v: w.shards[w.store.place(v)].partition
        part("blk").create_vertex("blk", sg.next())
        for i in range(6):
            part(f"tx{i}").create_vertex(f"tx{i}", sg.next())
            e = part("blk").create_edge("blk", f"tx{i}", sg.next())
            if i % 2 == 0:
                part("blk").set_edge_prop("blk", e.eid, "type", "contains",
                                          sg.next())
            part(f"tx{i}").set_vertex_prop(f"tx{i}", "value", 10 * i,
                                           sg.next())
        at = sg.query()
        r_f, r_s, _, _ = self._both(w, "block_render",
                                    [("blk", {"hop": 0})], at)
        key = lambda d: (d["tx"], d["value"], d["n_out"])
        assert sorted(r_f, key=key) == sorted(r_s, key=key)
        assert {d["tx"] for d in r_f} == {f"tx{i}" for i in range(0, 6, 2)}

    def test_fallback_on_unsupported_params(self):
        """Unhashable filter constants force the scalar path (and the
        driver agrees with it)."""
        assert not REGISTRY["traverse"].frontier_ok(
            {"edge_property": ("rel", ["unhashable"])})
        w = make_weaver(1)
        sg = _Stamps(2)
        part = lambda v: w.shards[w.store.place(v)].partition
        for v in "ab":
            part(v).create_vertex(v, sg.next())
        part("a").create_edge("a", "b", sg.next())
        at = sg.query()
        r_f, r_s, _, _ = self._both(
            w, "traverse",
            [("a", {"depth": 0, "edge_property": ("rel", ["unhashable"])})],
            at)
        assert r_f == r_s == ["a"]


class TestFrontierMessaging:
    def _social(self, w, n=60, m=400, seed=0):
        rng = np.random.default_rng(seed)
        tx = w.begin_tx()
        for i in range(n):
            tx.create_vertex(f"u{i}")
        seen = set()
        for _ in range(m):
            a, b = rng.integers(0, n, 2)
            if a != b and (a, b) not in seen:
                seen.add((a, b))
                tx.create_edge(f"u{a}", f"u{b}")
        assert w.run_tx(tx).ok

    def test_entry_collapse_vs_scalar(self):
        """Same query, same graph: the batched path delivers packed
        frontiers (dedup'd entries), the scalar path one entry per
        emitted vertex."""
        w_f = Weaver(WeaverConfig(n_gatekeepers=2, n_shards=4, seed=9,
                                  frontier_progs=True))
        w_s = Weaver(WeaverConfig(n_gatekeepers=2, n_shards=4, seed=9,
                                  frontier_progs=False))
        self._social(w_f)
        self._social(w_s)
        r_f, _, _ = w_f.run_program("traverse", [("u0", {"depth": 0})],
                                    timeout=60.0)
        r_s, _, _ = w_s.run_program("traverse", [("u0", {"depth": 0})],
                                    timeout=60.0)
        assert r_f == r_s and len(r_f) > 10
        c_f, c_s = w_f.counters(), w_s.counters()
        assert c_f["frontier_batches"] > 0
        assert c_f["scalar_deliveries"] == 0
        assert c_s["frontier_batches"] == 0
        # packed frontiers dedup per (hop, shard): strictly fewer entries
        assert c_f["prog_entries_delivered"] < c_s["prog_entries_delivered"]
        # per-hop message count is O(shards): each delivery emits at most
        # one message per destination shard, so total deliveries are
        # bounded by shards^2 per hop — while the scalar path's payload
        # grows with emitted vertices
        st = w_f.coordinator.last_prog_stats
        assert st["batches"] == c_f["frontier_batches"]
        assert st["entries"] == c_f["prog_entries_delivered"]

    def test_results_identical_both_paths_end_to_end(self):
        for name, entries in [
            ("get_node", [("u1", None)]),
            ("count_edges", [("u2", None)]),
            ("reachable", [("u0", {"target": "u41"})]),
            ("sssp", [("u0", {"target": "u17", "max_depth": 64})]),
        ]:
            w_f = Weaver(WeaverConfig(n_gatekeepers=2, n_shards=3, seed=4,
                                      frontier_progs=True))
            w_s = Weaver(WeaverConfig(n_gatekeepers=2, n_shards=3, seed=4,
                                      frontier_progs=False))
            self._social(w_f, seed=2)
            self._social(w_s, seed=2)
            r_f, _, _ = w_f.run_program(name, entries, timeout=60.0)
            r_s, _, _ = w_s.run_program(name, entries, timeout=60.0)
            assert r_f == r_s, (name, r_f, r_s)


class TestPropColumns:
    def test_engine_vertex_prop_exposure(self):
        """SnapshotEngine property columns == dict-path prop_at."""
        w = make_weaver(0)
        sg = _Stamps(2)
        part = lambda v: w.shards[w.store.place(v)].partition
        rng = np.random.default_rng(0)
        for i in range(12):
            part(f"p{i}").create_vertex(f"p{i}", sg.next())
        for i in range(12):
            for _ in range(int(rng.integers(0, 3))):   # versions
                part(f"p{i}").set_vertex_prop(f"p{i}", "rank",
                                              int(rng.integers(0, 100)),
                                              sg.next())
        mid = sg.query()
        for i in range(0, 12, 2):                      # later versions
            part(f"p{i}").set_vertex_prop(f"p{i}", "rank", 777, sg.next())
        eng = SnapshotEngine(w)
        ga = eng.snapshot(mid)
        vals, num = eng.vertex_prop_column("rank")
        for i, vid in enumerate(ga.vids):
            p = part(vid)
            want = p.vertex_prop_at(vid, "rank", mid)
            assert vals[i] == want
            if want is not None:
                assert num[i] == float(want)
        # later stamp sees the overwrites
        at2 = sg.query()
        eng.snapshot(at2)
        vals2, _ = eng.vertex_prop_column("rank")
        for i, vid in enumerate(ga.vids):
            assert vals2[i] == part(vid).vertex_prop_at(vid, "rank", at2)

    def test_engine_edge_prop_exposure(self):
        w = make_weaver(0)
        sg = _Stamps(2)
        part = lambda v: w.shards[w.store.place(v)].partition
        part("a").create_vertex("a", sg.next())
        part("b").create_vertex("b", sg.next())
        e1 = part("a").create_edge("a", "b", sg.next())
        part("a").set_edge_prop("a", e1.eid, "rel", "OWNS", sg.next())
        part("a").set_edge_prop("a", e1.eid, "rel", "LIKES", sg.next())
        eng = SnapshotEngine(w)
        eng.snapshot(sg.query())
        got = eng.edge_prop_rows("rel")
        assert list(got.values()) == ["LIKES"]

    def test_props_purged_on_recreate(self):
        """Dict path drops property history on vertex re-create; the
        columns must agree (no resurrection on the data plane)."""
        w = make_weaver(0)
        sg = _Stamps(2)
        part = lambda v: w.shards[w.store.place(v)].partition
        part("x").create_vertex("x", sg.next())
        part("x").set_vertex_prop("x", "value", 41, sg.next())
        part("x").delete_vertex("x", sg.next())
        part("x").create_vertex("x", sg.next())
        at = sg.query()
        eng = SnapshotEngine(w)
        eng.snapshot(at)
        vals, _ = eng.vertex_prop_column("value")
        assert vals[eng.index["x"]] is None
        assert part("x").vertex_prop_at("x", "value", at) is None


class TestCompactionDelta:
    def test_gc_compaction_delta_interleaved(self):
        """Churn + GC + forced-threshold compaction, with a warm engine
        delta-refreshing throughout: results always equal cold + seed
        reference, and the warm engine NEVER rebuilds cold (vertex
        deletes ride the tombstone/backfill path, compactions the event
        remap path)."""
        rng = np.random.default_rng(7)
        w = make_weaver(7)
        sg = _Stamps(2)
        live, edges = set(), []
        warm = SnapshotEngine(w)
        compactions = 0

        def canon(ga):
            vids = ga.vids[:ga.n_nodes]
            return (sorted(vids),
                    sorted(zip((vids[i] for i in ga.edge_src.tolist()),
                               (vids[i] for i in ga.edge_dst.tolist()))))

        for round_i in range(10):
            mutate(rng, w, sg, live, edges, round_i)
            if round_i % 2 == 1:
                horizon = Stamp(0, tuple(sg.clock), -1, 0)
                for sh in w.shards:
                    sh.partition.collect(horizon)
                    # force compaction at ANY dead fraction
                    cols = sh.partition.columns
                    if cols.dead_fraction() > 0:
                        cols.compact()
            at = sg.query()
            delta = warm.snapshot(at)
            cold = SnapshotEngine(w).snapshot(at)
            ref = A.snapshot_arrays_python(w, at)
            assert canon(delta) == canon(cold) == canon(ref), round_i
            compactions = sum(sh.partition.columns.n_compactions
                              for sh in w.shards)
        assert compactions > 0, "compaction never exercised"
        assert warm.stats["cold"] == 1, "delta path fell back to cold"
        assert warm.stats["delta"] > 0

    def test_compact_remaps_slots_and_props(self):
        from repro.core.mvgraph import MVGraphPartition
        p = MVGraphPartition(2)
        s = _Stamps(2)
        for i in range(6):
            p.create_vertex(f"n{i}", s.next())
        e = p.create_edge("n5", "n0", s.next())
        p.set_edge_prop("n5", e.eid, "weight", 3.0, s.next())
        p.set_vertex_prop("n5", "value", 9, s.next())
        for i in range(4):
            p.delete_vertex(f"n{i}", s.next())
        p.collect(Stamp(0, (999, 999), -1, 0))
        cols = p.columns
        cols.compact()
        assert cols.n_v == 2 and len(cols.events) >= 1
        # slot dicts renumbered; writes after compaction still work
        p.create_vertex("n9", s.next())
        e2 = p.create_edge("n9", "n5", s.next())
        p.delete_edge("n9", e2.eid, s.next())
        # prop rows survived with remapped owners
        assert cols.e_props.n == 1 and cols.v_props.n == 1
        ow = int(cols.e_props.owner.view()[0])
        assert cols.e_src.view()[ow] == cols.intern.intern("n5")


class TestSortedSegmentOps:
    @pytest.mark.parametrize("n", [1, 100, 128])
    @pytest.mark.parametrize("op", ["min", "max", "sum"])
    def test_matches_dense_reference(self, op, n):
        from repro.kernels.segment_mp import ops as smp
        rng = np.random.default_rng(3)
        keys = np.sort(rng.integers(0, 20, n))
        vals = rng.normal(size=n)
        uniq, red = smp.segment_reduce_sorted(vals, keys, op)
        np.testing.assert_array_equal(uniq, np.unique(keys))
        # float64 in, float64 out: the reduction never rounds
        assert red.dtype == np.float64
        fn = {"min": np.min, "max": np.max, "sum": np.sum}[op]
        for k, r in zip(uniq.tolist(), red.tolist()):
            np.testing.assert_allclose(r, fn(vals[keys == k]), rtol=1e-12)

    def test_empty(self):
        from repro.kernels.segment_mp import ops as smp
        u, r = smp.segment_reduce_sorted(np.zeros(0), np.zeros(0, np.int64))
        assert u.size == 0 and r.size == 0


class TestSortedPipelineBatches:
    def test_pipeline_emits_dst_sorted(self):
        from repro.data.pipeline import DynamicGraphPipeline
        w = Weaver(WeaverConfig(n_gatekeepers=2, n_shards=2, seed=1))
        tx = w.begin_tx()
        for i in range(10):
            tx.create_vertex(f"v{i}")
        rng = np.random.default_rng(0)
        for _ in range(25):
            a, b = rng.integers(0, 10, 2)
            if a != b:
                tx.create_edge(f"v{a}", f"v{b}")
        assert w.run_tx(tx).ok
        pipe = DynamicGraphPipeline(w, d_feat=4, n_classes=2,
                                    pad_nodes=16, pad_edges=64)
        sb = pipe.snapshot_batch()
        assert np.all(np.diff(sb.edge_dst) >= 0), "dst not sorted"
        # sorted-claim reductions agree with the unsorted baseline
        import jax.numpy as jnp
        from repro.models import mp
        msgs = jnp.asarray(rng.normal(size=(sb.edge_dst.size, 3))
                           .astype(np.float32))
        base = np.asarray(mp.scatter_sum(msgs, jnp.asarray(sb.edge_dst), 16))
        srt = np.asarray(mp.scatter_sum(msgs, jnp.asarray(sb.edge_dst), 16,
                                        sorted_ids=True))
        np.testing.assert_allclose(base, srt, rtol=1e-6)
        x = jnp.asarray(rng.normal(size=(16, 3)).astype(np.float32))
        wm = jnp.asarray(rng.normal(size=(3, 3)).astype(np.float32))
        p0 = np.asarray(mp.propagate_matmul(x, wm,
                                            jnp.asarray(sb.edge_src),
                                            jnp.asarray(sb.edge_dst), 16))
        p1 = np.asarray(mp.propagate_matmul(x, wm,
                                            jnp.asarray(sb.edge_src),
                                            jnp.asarray(sb.edge_dst), 16,
                                            dst_sorted=True))
        np.testing.assert_allclose(p0, p1, rtol=1e-5)

    def test_module_default_flag(self):
        from repro.models import mp
        try:
            mp.set_sorted_indices(True)
            assert mp._sorted(False) is True
        finally:
            mp.set_sorted_indices(False)
        assert mp._sorted(False) is False
