"""Tests for the JAX analytics bridge (snapshot -> arrays -> traversals)."""
import heapq

import numpy as np
import pytest
import jax.numpy as jnp

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis "
    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

import networkx as nx

from repro.core import Weaver, WeaverConfig
from repro.core import analytics as A
from repro.core.clock import Stamp


def _random_edges(rng, n, m):
    src = rng.integers(0, n, size=m).astype(np.int32)
    dst = rng.integers(0, n, size=m).astype(np.int32)
    return src, dst


def _rmat_edges(rng, scale):
    """R-MAT (0.57/0.19/0.19/0.05) over 2**scale permuted ids, edge factor
    8, one direction."""
    src = np.zeros(8 * (1 << scale), np.int64)
    dst = np.zeros_like(src)
    for _ in range(scale):
        q = rng.choice(4, src.size, p=[0.57, 0.19, 0.19, 0.05])
        src, dst = 2 * src + q // 2, 2 * dst + q % 2
    perm = rng.permutation(1 << scale)
    return perm[src], perm[dst]


def _bfs_case(case):
    """(src, dst, n, sources, max_iters) of a tiled-BFS test case."""
    rng = np.random.default_rng(11)
    if case in ("rmat", "sources", "capped"):
        # 512 R-MAT ids, both directions, and 88 vertices with no edges
        src, dst = _rmat_edges(rng, 9)
        src, dst, n = np.r_[src, dst], np.r_[dst, src], 600
        roots = {"rmat": [int(src[0])],
                 "sources": [int(src[0]), int(dst[-1]), 599, int(src[0])],
                 "capped": [int(src[0])]}[case]
        return src, dst, n, roots, 2 if case == "capped" else None
    if case == "random":
        src, dst = _random_edges(rng, 700, 3000)
        return src, dst, 700, [0], None
    if case == "unreachable":
        # edges among the first 200 vertices, 250 -> 0 (250 is never
        # reached) and vertices 200..299 otherwise isolated
        src, dst = _random_edges(rng, 200, 600)
        return np.r_[src, 250], np.r_[dst, 0], 300, [0], None
    if case == "empty":
        return np.zeros(0, np.int64), np.zeros(0, np.int64), 40, [3], None
    # "mid_chunk": two whole 1,024-slot chunks and one edge of a third
    src, dst = _random_edges(rng, 500, 2 * 1024 + 1)
    return src, dst, 500, [int(src[0])], None


GRAPH_SHAPES = ["empty", "isolated", "self_loops", "duplicates",
                "two_components", "rmat"]


def _graph_case(shape):
    """(src, dst, n) of a library-kernel test graph."""
    rng = np.random.default_rng(5)
    if shape == "empty":
        return np.zeros(0, np.int64), np.zeros(0, np.int64), 12
    if shape == "isolated":
        # edges among the first 30 vertices; 30..49 have none
        src, dst = _random_edges(rng, 30, 60)
        return src, dst, 50
    if shape == "self_loops":
        # 15 self-loops over 40 vertices; 39's only edge is its loop
        src, dst = _random_edges(rng, 39, 80)
        loops = np.r_[rng.integers(0, 39, 14), 39]
        return np.r_[src, loops], np.r_[dst, loops], 40
    if shape == "duplicates":
        src, dst = _random_edges(rng, 40, 50)
        return np.r_[src, src, src[:10]], np.r_[dst, dst, dst[:10]], 40
    if shape == "two_components":
        a_src, a_dst = _random_edges(rng, 25, 80)
        b_src, b_dst = _random_edges(rng, 25, 80)
        return np.r_[a_src, b_src + 25], np.r_[a_dst, b_dst + 25], 50
    # Graphalytics' Graph500 R-MAT at scale 8, edge factor 8, both
    # directions (the draw of test_device_shard's ``rmat_csc``)
    src, dst = _rmat_edges(rng, 8)
    return np.r_[src, dst], np.r_[dst, src], 256


def _components_ref(src, dst, n):
    """Union-find over the undirected edges: each vertex's label is the
    least vertex id of its component."""
    parent = list(range(n))

    def find(v):
        while parent[v] != v:
            parent[v] = parent[parent[v]]
            v = parent[v]
        return v

    for u, v in zip(src.tolist(), dst.tolist()):
        ru, rv = find(u), find(v)
        if ru != rv:
            parent[max(ru, rv)] = min(ru, rv)
    return np.array([find(v) for v in range(n)])


def _dijkstra_ref(src, dst, w, n, source):
    """float64 Dijkstra over the directed weighted edges; inf where
    unreachable."""
    adj = [[] for _ in range(n)]
    for u, v, x in zip(src.tolist(), dst.tolist(), w.tolist()):
        adj[u].append((v, x))
    dist = np.full(n, np.inf)
    dist[source] = 0.0
    heap = [(0.0, source)]
    while heap:
        d, u = heapq.heappop(heap)
        if d > dist[u]:
            continue
        for v, x in adj[u]:
            if d + x < dist[v]:
                dist[v] = d + x
                heapq.heappush(heap, (d + x, v))
    return dist


class TestLibraryKernels:
    """The library graph kernels no served request runs, against plain
    references on graphs with no edges, isolated vertices, self-loops,
    duplicate edges, two components and an R-MAT draw."""

    @pytest.mark.parametrize("shape", GRAPH_SHAPES)
    def test_connected_components_ga_matches_union_find(self, shape):
        """Exactly the least vertex id of each weak component, through
        the snapshot entry point (CSR order, src-sorted claim)."""
        src, dst, n = _graph_case(shape)
        o = np.lexsort((dst, src))
        ga = A.GraphArrays(vids=list(range(n)), index={},
                           edge_src=src[o].astype(np.int32),
                           edge_dst=dst[o].astype(np.int32), n_nodes=n)
        got = np.asarray(A.connected_components_ga(ga))
        want = _components_ref(src, dst, n)
        np.testing.assert_array_equal(got, want)
        if shape == "two_components":
            assert set(got.tolist()) == {0, 25}

    @pytest.mark.parametrize("shape", GRAPH_SHAPES)
    def test_sssp_weighted_matches_dijkstra(self, shape):
        """Within float32 rounding of a float64 Dijkstra from vertex 0
        (a path of k edges: k float32 additions, each within 2**-24 of
        its sum); the float32 sentinel exactly where nothing reaches."""
        src, dst, n = _graph_case(shape)
        rng = np.random.default_rng(n)
        w = rng.uniform(0.5, 4.0, src.size).astype(np.float32)
        got = np.asarray(A.sssp_weighted(
            jnp.asarray(src.astype(np.int32)),
            jnp.asarray(dst.astype(np.int32)), jnp.asarray(w), n,
            jnp.asarray([0])))
        want = _dijkstra_ref(src, dst, w.astype(np.float64), n, 0)
        reach = np.isfinite(want)
        np.testing.assert_array_equal(got < np.float32(3.4e38), reach)
        np.testing.assert_array_equal(got[~reach], np.float32(3.4e38))
        np.testing.assert_allclose(got[reach], want[reach], rtol=1e-6)
        if shape == "two_components":
            assert not reach[25:].any()


class TestFrontierPrograms:
    def test_bfs_levels_line_graph(self):
        src = np.array([0, 1, 2], dtype=np.int32)
        dst = np.array([1, 2, 3], dtype=np.int32)
        lv = np.asarray(A.bfs_levels(jnp.asarray(src), jnp.asarray(dst), 4,
                                     jnp.asarray([0])))
        assert lv.tolist() == [0, 1, 2, 3]

    def test_bfs_unreachable_is_inf(self):
        src = np.array([0], dtype=np.int32)
        dst = np.array([1], dtype=np.int32)
        lv = np.asarray(A.bfs_levels(jnp.asarray(src), jnp.asarray(dst), 3,
                                     jnp.asarray([0])))
        assert lv[2] == A.INF

    @given(st.integers(2, 30), st.integers(1, 80), st.integers(0, 2**31 - 1))
    @settings(max_examples=25, deadline=None)
    def test_bfs_matches_networkx(self, n, m, seed):
        rng = np.random.default_rng(seed)
        src, dst = _random_edges(rng, n, m)
        g = nx.DiGraph()
        g.add_nodes_from(range(n))
        g.add_edges_from(zip(src.tolist(), dst.tolist()))
        ref = nx.single_source_shortest_path_length(g, 0)
        lv = np.asarray(A.bfs_levels(jnp.asarray(src), jnp.asarray(dst), n,
                                     jnp.asarray([0])))
        for v in range(n):
            if v in ref:
                assert lv[v] == ref[v], (v, lv[v], ref[v])
            else:
                assert lv[v] == A.INF

    @given(st.integers(2, 25), st.integers(0, 60), st.integers(0, 2**31 - 1))
    @settings(max_examples=25, deadline=None)
    def test_connected_components_match_networkx(self, n, m, seed):
        rng = np.random.default_rng(seed)
        src, dst = _random_edges(rng, n, m)
        g = nx.Graph()
        g.add_nodes_from(range(n))
        g.add_edges_from(zip(src.tolist(), dst.tolist()))
        lab = np.asarray(A.connected_components(jnp.asarray(src),
                                                jnp.asarray(dst), n))
        for comp in nx.connected_components(g):
            labs = {int(lab[v]) for v in comp}
            assert len(labs) == 1

    def test_pagerank_sums_to_one(self):
        rng = np.random.default_rng(0)
        src, dst = _random_edges(rng, 50, 300)
        pr = np.asarray(A.pagerank(jnp.asarray(src), jnp.asarray(dst), 50))
        # dangling mass leaks in this formulation only if a node has no
        # out-edges; with 300 random edges over 50 nodes that's unlikely
        assert pr.min() > 0

    @pytest.mark.parametrize("gather", ["mxu", "xla"])
    def test_tiled_pagerank_matches_f64_loop(self, monkeypatch, request,
                                             gather):
        """``pagerank_ga`` on the tiled kernel path (interpreted here) on a
        small R-MAT graph, both directions, plus vertices with no edges
        and a few with in-edges only (dangling): within float32 of a
        float64 loop of the same formulation, and counted.  ``xla``: a
        graph past ``GATHER_MAX_N`` takes XLA's gather."""
        import jax
        from repro.kernels.segment_sum import kernel
        if gather == "xla":
            monkeypatch.setattr(kernel, "GATHER_MAX_N", 0)
            # pagerank_tiled is traced anew here and after the test
            jax.clear_caches()
            request.addfinalizer(jax.clear_caches)
        rng = np.random.default_rng(7)
        n = 600                               # 512 R-MAT ids + 88 more
        src, dst = _rmat_edges(rng, 9)
        src, dst = np.r_[src, dst, [0, 1, 2]], np.r_[dst, src, [597] * 3]
        ga = A.GraphArrays(vids=list(range(n)), index={},
                           edge_src=src.astype(np.int32),
                           edge_dst=dst.astype(np.int32), n_nodes=n)
        deg = np.maximum(np.bincount(src, minlength=n), 1).astype(float)
        want = np.full(n, 1.0 / n)
        for _ in range(20):
            agg = np.zeros(n)
            np.add.at(agg, dst, want[src] / deg[src])
            want = 0.15 / n + 0.85 * agg
        before = dict(A.pagerank_stats)
        monkeypatch.setattr(A, "FORCE_KERNEL", True)
        got = np.asarray(A.pagerank_ga(ga))
        np.testing.assert_allclose(got, want, rtol=1e-5)
        after = A.pagerank_stats
        assert after["tiled_calls"] == before["tiled_calls"] + 1
        assert after["xla_calls"] == before["xla_calls"]
        assert after["edges"] - before["edges"] == src.size
        assert after["tiles"] > before["tiles"]
        monkeypatch.setattr(A, "FORCE_KERNEL", False)
        np.testing.assert_allclose(np.asarray(A.pagerank_ga(ga)), want,
                                   rtol=1e-5)
        assert A.pagerank_stats["xla_calls"] == before["xla_calls"] + 1

    @pytest.mark.parametrize("case", ["rmat", "random", "sources",
                                      "unreachable", "empty", "mid_chunk",
                                      "capped"])
    def test_tiled_bfs_matches_plain_and_reference(self, monkeypatch, case):
        """``bfs_levels_ga`` on the tiled kernel path (interpreted here):
        the frontier-bit gather and segment sum give exactly the levels of
        the min-relaxation ``bfs_levels`` and of the benchmark's numpy
        reference (several sources: the least level over them; a
        ``max_iters`` cap: INF past it), and the call is counted.
        PageRank and BFS on one snapshot build its tile layout once."""
        from bench.harness import reference
        from repro.kernels.segment_sum import ops as seg

        src, dst, n, roots, max_iters = _bfs_case(case)
        o = np.lexsort((src, dst))
        src, dst = src[o].astype(np.int32), dst[o].astype(np.int32)
        ga = A.GraphArrays(vids=list(range(n)), index={}, edge_src=src,
                           edge_dst=dst, n_nodes=n, _csc=(src, dst))
        layouts = []
        tile_layout = seg.tile_layout
        monkeypatch.setattr(seg, "tile_layout",
                            lambda *a: layouts.append(a) or tile_layout(*a))
        monkeypatch.setattr(A, "FORCE_KERNEL", True)
        before = dict(A.bfs_stats)
        A.pagerank_ga(ga)
        got = np.asarray(A.bfs_levels_ga(ga, roots, max_iters))
        assert len(layouts) == 1
        assert A.bfs_stats == {"tiled_calls": before["tiled_calls"] + 1,
                               "xla_calls": before["xla_calls"]}
        plain = A.bfs_levels(jnp.asarray(src), jnp.asarray(dst), n,
                             jnp.asarray(roots), max_iters)
        np.testing.assert_array_equal(got, np.asarray(plain))
        lv = np.stack([reference.bfs_levels(src, dst, n, r) for r in roots])
        want = np.where(lv < 0, A.INF, lv).min(axis=0)
        if max_iters is not None:
            assert (want > max_iters).any()
            want = np.where(want > max_iters, A.INF, want)
        np.testing.assert_array_equal(got, want)
        if case == "unreachable":
            assert (got[200:] == A.INF).all()

    def test_sssp_weighted(self):
        src = np.array([0, 0, 1], dtype=np.int32)
        dst = np.array([1, 2, 2], dtype=np.int32)
        w = np.array([1.0, 5.0, 1.0], dtype=np.float32)
        d = np.asarray(A.sssp_weighted(jnp.asarray(src), jnp.asarray(dst),
                                       jnp.asarray(w), 3, jnp.asarray([0])))
        assert d[2] == pytest.approx(2.0)

    def test_clustering_jax_matches_np(self):
        rng = np.random.default_rng(1)
        src, dst = _random_edges(rng, 20, 80)
        keep = src != dst
        src, dst = src[keep], dst[keep]
        # dedupe parallel edges (the numpy reference uses sets)
        pairs = sorted(set(zip(src.tolist(), dst.tolist())))
        src = np.asarray([p[0] for p in pairs], np.int32)
        dst = np.asarray([p[1] for p in pairs], np.int32)
        ref = A.clustering_coefficients_np(src, dst, 20)
        got = np.asarray(A.clustering_coefficients_jax(src, dst, 20,
                                                       max_deg=20))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


class TestSnapshotBridge:
    def test_snapshot_matches_node_program(self):
        w = Weaver(WeaverConfig(n_gatekeepers=2, n_shards=3, seed=2))
        tx = w.begin_tx()
        for v in "abcde":
            tx.create_vertex(v)
        for s, d in [("a", "b"), ("b", "c"), ("c", "d"), ("a", "e")]:
            tx.create_edge(s, d)
        assert w.run_tx(tx).ok
        # delete one edge
        eid = [e for e, dd in w.read_vertex("b")["edges"].items()][0]
        tx2 = w.begin_tx()
        tx2.delete_edge("b", eid)
        assert w.run_tx(tx2).ok

        res, stamp, _ = w.run_program("traverse", [("a", {"depth": 0})])
        ga = A.snapshot_arrays(w, stamp)
        lv = np.asarray(A.bfs_levels(jnp.asarray(ga.edge_src),
                                     jnp.asarray(ga.edge_dst), ga.n_nodes,
                                     jnp.asarray([ga.index["a"]])))
        reachable = sorted(ga.vids[i] for i in range(ga.n_nodes)
                           if lv[i] < A.INF)
        assert reachable == res

    def test_visibility_kernel_path_matches(self):
        w = Weaver(WeaverConfig(n_gatekeepers=2, n_shards=2, seed=3))
        tx = w.begin_tx()
        for v in "xyz":
            tx.create_vertex(v)
        e1 = tx.create_edge("x", "y")
        tx.create_edge("y", "z")
        assert w.run_tx(tx).ok
        tx2 = w.begin_tx()
        tx2.delete_edge(e1)
        assert w.run_tx(tx2).ok
        res, stamp, _ = w.run_program("count_edges", [("x", None)])
        assert res == 0
        ga = A.snapshot_arrays(w, stamp, keep_raw=True)
        vsrc, vdst, mask = A.visible_edges_at(ga, stamp,
                                              w.cfg.n_gatekeepers)
        # filtered edges equal the snapshot edge list
        got = sorted(zip(vsrc.tolist(), vdst.tolist()))
        want = sorted(zip(ga.edge_src.tolist(), ga.edge_dst.tolist()))
        assert got == want


class TestBaselines:
    def test_twopl_store_basic(self):
        from repro.core.twopl import TwoPLStore
        s = TwoPLStore(n_shards=3, seed=0)
        s.load_graph([("a", "b"), ("b", "c")])
        done = []
        s.submit([{"op": "get_vertex", "vid": "a"}], done.append)
        s.sim.run(until=0.1)
        assert done and done[0]["ok"]
        assert done[0]["reads"]["a"]["edges"]

    def test_twopl_contention_serializes(self):
        from repro.core.twopl import TwoPLStore
        s = TwoPLStore(n_shards=2, seed=0)
        s.load_graph([("h", "x")])
        done = []
        for i in range(10):
            s.submit([{"op": "set_vertex_prop", "vid": "h", "key": "k",
                       "value": i}], done.append)
        s.sim.run(until=1.0)
        assert len(done) == 10
        assert s.sim.counters.lock_waits > 0

    def test_bsp_sync_and_async_reach_target(self):
        from repro.core.bsp import BSPEngine
        e = BSPEngine(n_workers=3, seed=0)
        e.load_graph([(f"v{i}", f"v{i+1}") for i in range(20)])
        out = []
        e.bfs_sync("v0", "v20", out.append)
        e.sim.run(until=1.0)
        assert out and out[0]["reached"]
        assert out[0]["levels"] >= 20
        out2 = []
        e.bfs_async("v0", "v20", out2.append)
        e.sim.run(until=2.0)
        assert out2 and out2[0]["reached"]
