"""Columnar snapshot engine: delta-vs-cold equivalence under random
mutation streams, Pallas/numpy visibility bit-equality (incl. padded
tail), batched oracle refinement call counts, and the sorted-CSR helper
paths.  Seeded-random (no hypothesis dependency) so this file always
runs in the tier-1 suite."""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import Weaver, WeaverConfig
from repro.core import analytics as A
from repro.core import clock
from repro.core.analytics import SnapshotEngine
from repro.core.clock import NO_STAMP, Stamp


def canon(ga):
    """Order-free canonical form: vid set + vid-pair edge multiset."""
    vids = ga.vids[:ga.n_nodes]
    pairs = sorted(zip((vids[i] for i in ga.edge_src.tolist()),
                       (vids[i] for i in ga.edge_dst.tolist())))
    return sorted(vids), pairs


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


class TestDeltaEqualsCold:
    def _mutate(self, rng, w, sg, live, dead, edges, round_i):
        part = lambda v: w.shards[w.store.place(v)].partition
        for _ in range(int(rng.integers(1, 25))):
            op = rng.integers(0, 100)
            if op < 35 or not live:                      # create vertex
                vid = f"v{round_i}_{rng.integers(0, 1 << 30)}"
                if vid in live or vid in dead:
                    continue
                part(vid).create_vertex(vid, sg.next())
                live.add(vid)
            elif op < 65:                                # create edge
                s = str(rng.choice(sorted(live)))
                d = str(rng.choice(sorted(live | dead)))
                e = part(s).create_edge(s, d, sg.next())
                edges.append((s, e.eid))
            elif op < 75 and edges:                      # delete edge
                s, eid = edges[int(rng.integers(0, len(edges)))]
                if s not in live:
                    continue
                e = part(s).vertices[s].out_edges.get(eid)
                if e is not None and e.delete_ts is None:
                    part(s).delete_edge(s, eid, sg.next())
            elif op < 85 and len(live) > 1:              # delete vertex
                vid = str(rng.choice(sorted(live)))
                part(vid).delete_vertex(vid, sg.next())
                live.discard(vid)
                dead.add(vid)
            elif op < 92 and dead:                       # re-create
                vid = str(rng.choice(sorted(dead)))
                part(vid).create_vertex(vid, sg.next())
                dead.discard(vid)
                live.add(vid)
            else:                                        # GC at now
                horizon = Stamp(0, tuple(sg.clock), -1, 0)
                for sh in w.shards:
                    sh.partition.collect(horizon)

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_random_mutation_stream(self, seed):
        rng = np.random.default_rng(seed)
        w = Weaver(WeaverConfig(n_gatekeepers=2, n_shards=3, gc_period=0,
                                seed=seed))
        sg = _Stamps(w.cfg.n_gatekeepers)
        live, dead, edges = set(), set(), []
        warm = SnapshotEngine(w)          # refreshed incrementally
        for round_i in range(12):
            self._mutate(rng, w, sg, live, dead, edges, round_i)
            at = sg.query()
            delta = warm.snapshot(at)
            cold = SnapshotEngine(w).snapshot(at)
            ref = A.snapshot_arrays_python(w, at)
            assert canon(delta) == canon(cold) == canon(ref)
            # CSR/CSC invariants on the incremental snapshot
            k = (delta.edge_src.astype(np.int64) << 32) | delta.edge_dst
            assert np.all(np.diff(k) >= 0)
            k2 = (delta.csc_dst.astype(np.int64) << 32) | delta.csc_src
            assert np.all(np.diff(k2) >= 0)
            assert (sorted(zip(delta.csc_src.tolist(),
                               delta.csc_dst.tolist()))
                    == sorted(zip(delta.edge_src.tolist(),
                                  delta.edge_dst.tolist())))
        assert warm.stats["delta"] + warm.stats["delta_noop"] > 0

    def test_noop_refresh_reuses_arrays(self):
        w = Weaver(WeaverConfig(n_gatekeepers=2, n_shards=2, gc_period=0,
                                seed=0))
        sg = _Stamps(2)
        part = lambda v: w.shards[w.store.place(v)].partition
        for v in "abc":
            part(v).create_vertex(v, sg.next())
        part("a").create_edge("a", "b", sg.next())
        eng = SnapshotEngine(w)
        g1 = eng.snapshot(sg.query())
        g2 = eng.snapshot(sg.query())
        assert g2.edge_src is g1.edge_src       # zero-copy noop refresh
        assert eng.stats["delta_noop"] == 1

    def test_weaver_end_to_end_with_cache(self):
        """Through the real transaction pipeline, snapshots at successive
        program stamps (cache active) match the seed reference."""
        w = Weaver(WeaverConfig(n_gatekeepers=2, n_shards=3, seed=5))
        tx = w.begin_tx()
        for i in range(12):
            tx.create_vertex(f"n{i}")
        for i in range(11):
            tx.create_edge(f"n{i}", f"n{i+1}")
        assert w.run_tx(tx).ok
        for step in range(4):
            tx = w.begin_tx()
            tx.create_edge(f"n{step}", f"n{11 - step}")
            if step == 2:
                tx.delete_vertex("n7")
            assert w.run_tx(tx).ok
            _, stamp, _ = w.run_program("count_edges", [("n0", None)])
            got = A.snapshot_arrays(w, stamp)
            want = A.snapshot_arrays_python(w, stamp)
            assert canon(got) == canon(want)


class TestBatchedRefinement:
    def test_oracle_calls_no_higher_than_seed(self):
        """Stamps truly concurrent with the query are refined through ONE
        oracle request; the seed path pays one per object."""
        w = Weaver(WeaverConfig(n_gatekeepers=2, n_shards=2, gc_period=0,
                                seed=0))
        part = lambda v: w.shards[w.store.place(v)].partition
        # writes advance only gk0's component; the query stamp advances
        # only gk1's -> vector-incomparable (paper Fig. 5 shape)
        for i in range(6):
            s = Stamp(0, (i + 1, 0), 0, i + 1)
            part(f"c{i}").create_vertex(f"c{i}", s)
        q = Stamp(0, (0, 9), 1, 9)

        base = w.sim.counters.oracle_calls
        ref = A.snapshot_arrays_python(w, q)
        seed_calls = w.sim.counters.oracle_calls - base

        base = w.sim.counters.oracle_calls
        got = SnapshotEngine(w).snapshot(q)
        col_calls = w.sim.counters.oracle_calls - base

        assert canon(got) == canon(ref)
        assert seed_calls >= 6          # one refine per concurrent object
        assert 1 <= col_calls <= seed_calls

    def test_conservative_mode_skips_oracle(self):
        w = Weaver(WeaverConfig(n_gatekeepers=2, n_shards=2, gc_period=0,
                                seed=0))
        part = lambda v: w.shards[w.store.place(v)].partition
        part("x").create_vertex("x", Stamp(0, (1, 0), 0, 1))
        q = Stamp(0, (0, 5), 1, 5)
        base = w.sim.counters.oracle_calls
        got = SnapshotEngine(w).snapshot(q, refine_concurrent=False)
        assert w.sim.counters.oracle_calls == base
        ref = A.snapshot_arrays_python(w, q, refine_concurrent=False)
        assert canon(got) == canon(ref)


class TestVisibilityKernelBitEquality:
    def _rows(self, rng, n, g, frac_no):
        rows = rng.integers(0, 6, size=(n, g + 1)).astype(np.int32)
        rows[:, 0] = rng.integers(0, 2, size=n)
        rows[rng.random(n) < frac_no] = NO_STAMP
        return rows

    @pytest.mark.parametrize("n", [1, 5, 127, 128, 129, 300])
    @pytest.mark.parametrize("g", [1, 3])
    def test_pallas_matches_np(self, n, g):
        from repro.kernels.mv_visibility import ops
        rng = np.random.default_rng(n * 7 + g)
        creates = self._rows(rng, n, g, 0.2)
        deletes = self._rows(rng, n, g, 0.5)
        q = np.asarray([1] + list(rng.integers(0, 6, g)), np.int32)
        want = clock.visibility_mask_np(creates, deletes, q)
        got = np.asarray(ops.visibility_mask(
            jnp.asarray(creates), jnp.asarray(deletes), jnp.asarray(q),
            block_n=128, interpret=True))
        np.testing.assert_array_equal(got, want)

    def test_padded_tail_is_invisible(self):
        """The pad rows the ops layer appends (all NO_STAMP) must come
        out False from the kernel itself."""
        from repro.kernels.mv_visibility.kernel import visibility_pallas
        rng = np.random.default_rng(0)
        n, g, block = 100, 2, 128
        creates = self._rows(rng, n, g, 0.0)
        deletes = self._rows(rng, n, g, 0.5)
        pad = block - n
        c_cm = np.pad(creates.T, ((0, 0), (0, pad)),
                      constant_values=NO_STAMP)
        d_cm = np.pad(deletes.T, ((0, 0), (0, pad)),
                      constant_values=NO_STAMP)
        q = np.asarray([1, 3, 3], np.int32)
        full = np.asarray(visibility_pallas(jnp.asarray(c_cm),
                                            jnp.asarray(d_cm),
                                            jnp.asarray(q),
                                            block_n=block, interpret=True))
        assert not full[n:].any()
        np.testing.assert_array_equal(
            full[:n], clock.visibility_mask_np(creates, deletes, q))

    def test_engine_kernel_path_matches_np_path(self):
        """FORCE_KERNEL=True routes the engine through the Pallas kernel
        (interpret on CPU); results must be identical."""
        def build():
            w = Weaver(WeaverConfig(n_gatekeepers=2, n_shards=2,
                                    gc_period=0, seed=0))
            sg = _Stamps(2)
            part = lambda v: w.shards[w.store.place(v)].partition
            for i in range(9):
                part(f"k{i}").create_vertex(f"k{i}", sg.next())
            for i in range(8):
                part(f"k{i}").create_edge(f"k{i}", f"k{i+1}", sg.next())
            part("k0").delete_edge("k0", 1, sg.next())
            return w, sg.query()

        w, q = build()
        got_np = SnapshotEngine(w).snapshot(q)
        old = A.FORCE_KERNEL
        A.FORCE_KERNEL = True
        try:
            got_k = SnapshotEngine(w).snapshot(q)
        finally:
            A.FORCE_KERNEL = old
        assert canon(got_np) == canon(got_k)
        np.testing.assert_array_equal(got_np.edge_src, got_k.edge_src)
        np.testing.assert_array_equal(got_np.edge_dst, got_k.edge_dst)


class TestSortedTraversalHelpers:
    def _snapshot(self, seed=0, n=30, m=120):
        rng = np.random.default_rng(seed)
        w = Weaver(WeaverConfig(n_gatekeepers=2, n_shards=3, gc_period=0,
                                seed=seed))
        sg = _Stamps(2)
        part = lambda v: w.shards[w.store.place(v)].partition
        for i in range(n):
            part(f"t{i}").create_vertex(f"t{i}", sg.next())
        for _ in range(m):
            s, d = rng.integers(0, n, 2)
            part(f"t{s}").create_edge(f"t{s}", f"t{d}", sg.next())
        return SnapshotEngine(w).snapshot(sg.query())

    def test_bfs_ga_matches_plain(self):
        ga = self._snapshot()
        src_i = ga.index["t0"]
        got = np.asarray(A.bfs_levels_ga(ga, [src_i]))
        want = np.asarray(A.bfs_levels(jnp.asarray(ga.edge_src),
                                       jnp.asarray(ga.edge_dst),
                                       ga.n_nodes, jnp.asarray([src_i])))
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("tiled", [False, True])
    def test_pagerank_ga_matches_plain(self, tiled, monkeypatch):
        ga = self._snapshot(seed=1)
        monkeypatch.setattr(A, "FORCE_KERNEL", tiled)
        got = np.asarray(A.pagerank_ga(ga))
        want = np.asarray(A.pagerank(jnp.asarray(ga.edge_src),
                                     jnp.asarray(ga.edge_dst), ga.n_nodes))
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_cc_ga_matches_plain(self):
        ga = self._snapshot(seed=2)
        got = np.asarray(A.connected_components_ga(ga))
        want = np.asarray(A.connected_components(jnp.asarray(ga.edge_src),
                                                 jnp.asarray(ga.edge_dst),
                                                 ga.n_nodes))
        np.testing.assert_array_equal(got, want)

    def test_indptr_lazy(self):
        ga = self._snapshot(seed=3)
        ip = ga.indptr
        assert ip.shape == (ga.n_nodes + 1,)
        assert ip[0] == 0 and ip[-1] == ga.edge_src.size
        for u in range(ga.n_nodes):
            assert np.all(ga.edge_src[ip[u]:ip[u + 1]] == u)


class TestClusteringCSR:
    @staticmethod
    def _reference(edge_src, edge_dst, n_nodes):
        nbrs = [set() for _ in range(n_nodes)]
        for s, d in zip(edge_src.tolist(), edge_dst.tolist()):
            if s != d:
                nbrs[s].add(d)
        out = np.zeros(n_nodes)
        for u in range(n_nodes):
            k = len(nbrs[u])
            if k < 2:
                continue
            links = sum(len(nbrs[v] & nbrs[u]) for v in nbrs[u])
            out[u] = links / (k * (k - 1))
        return out

    @pytest.mark.parametrize("seed", range(6))
    def test_matches_set_reference(self, seed):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(1, 50))
        m = int(rng.integers(0, 260))
        src = rng.integers(0, n, m).astype(np.int32)
        dst = rng.integers(0, n, m).astype(np.int32)
        got = A.clustering_coefficients_np(src, dst, n)
        np.testing.assert_allclose(got, self._reference(src, dst, n),
                                   rtol=1e-12)

    def test_build_csr(self):
        src = np.asarray([2, 0, 0, 1, 0], np.int32)
        dst = np.asarray([1, 2, 1, 1, 2], np.int32)
        indptr, nbrs = A.build_csr(src, dst, 3, dedup=True,
                                   drop_self_loops=True)
        assert indptr.tolist() == [0, 2, 2, 3]
        assert nbrs.tolist() == [1, 2, 1]
