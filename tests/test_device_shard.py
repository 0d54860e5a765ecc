"""Device-sharded columnar data plane (``device_shard_columns``).

The sharded visibility path must be *bit-identical* to the host-global
oracle under randomized churn — creates, deletes, re-creates, GC purges
and forced compaction remaps — because it is the same int32 comparison
over the same packed stamp rows, only resident per mesh device.

The equivalence body runs three ways:

* in-process on the default single CPU device (tier-1, always),
* in subprocesses under ``--xla_force_host_platform_device_count={2,4}``
  so ``shard_map`` really distributes blocks over multiple devices
  (jax locks the device count at first init, hence subprocesses; CI
  runs these under its forced-8-device stage as well).
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Shared equivalence body.  Drives IDENTICAL synthetic mutation streams
# into two Weavers (device-sharded vs host-global), snapshots both every
# round (warm/delta engines plus a cold engine on the sharded side) and
# asserts the columnar arrays are bit-equal.  Exercises GC purges and a
# forced compaction so CompactionEvent remaps flow through the plane's
# block re-upload path.
CHURN_SRC = '''
def churn_equivalence(seed, rounds=10, forced_compaction_round=5,
                      n_chips=1):
    import numpy as np
    from repro.core import Weaver, WeaverConfig
    from repro.core.analytics import SnapshotEngine
    from repro.core.clock import Stamp

    class Stamps:
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
            self.i += 1
            self.clock = [c + 1 for c in self.clock]
            return Stamp(0, tuple(self.clock), 0, self.clock[0])

    def mk(flag):
        # shards spread evenly over the chips; on one, an odd count
        return Weaver(WeaverConfig(n_gatekeepers=2,
                                   n_shards=3 if n_chips == 1 else 2 * n_chips,
                                   n_chips=n_chips, gc_period=0, seed=seed,
                                   device_shard_columns=flag))

    def apply(w, sg, op):
        part = lambda v: w.shards[w.store.place(v)].partition
        kind = op[0]
        if kind == "cv":
            part(op[1]).create_vertex(op[1], sg.next())
        elif kind == "ce":
            part(op[1]).create_edge(op[1], op[2], sg.next())
        elif kind == "de":
            s, eid = op[1], op[2]
            e = part(s).vertices[s].out_edges.get(eid)
            if e is not None and e.delete_ts is None:
                part(s).delete_edge(s, eid, sg.next())
        elif kind == "dv":
            part(op[1]).delete_vertex(op[1], sg.next())
        elif kind == "gc":
            horizon = Stamp(0, tuple(sg.clock), -1, 0)
            for sh in w.shards:
                sh.partition.collect(horizon)
        elif kind == "compact":
            for sh in w.shards:
                cols = sh.partition.columns
                if cols is not None:
                    cols.compact()

    w_dev, w_host = mk(True), mk(False)
    assert w_dev.device_plane is not None
    assert w_dev.device_plane.n_dev == n_chips
    assert w_host.device_plane is None
    sg_dev, sg_host = Stamps(2), Stamps(2)
    eng_dev, eng_host = SnapshotEngine(w_dev), SnapshotEngine(w_host)

    rng = np.random.default_rng(seed)
    live, dead, edges = set(), set(), []
    compacted = False
    for round_i in range(rounds):
        ops = []
        for _ in range(int(rng.integers(5, 25))):
            roll = rng.integers(0, 100)
            if roll < 35 or not live:
                vid = "v%d_%d" % (round_i, rng.integers(0, 1 << 30))
                if vid in live or vid in dead:
                    continue
                ops.append(("cv", vid))
                live.add(vid)
            elif roll < 65:
                s = str(rng.choice(sorted(live)))
                d = str(rng.choice(sorted(live | dead)))
                ops.append(("ce", s, d))
                edges.append((s, round_i))
            elif roll < 75 and edges:
                s, _ = edges[int(rng.integers(0, len(edges)))]
                if s in live:
                    ops.append(("de", s, 1))
            elif roll < 88 and len(live) > 1:
                vid = str(rng.choice(sorted(live)))
                ops.append(("dv", vid))
                live.discard(vid)
                dead.add(vid)
            else:
                ops.append(("gc",))
        if round_i == forced_compaction_round:
            ops.append(("gc",))
            ops.append(("compact",))
            compacted = True
        for op in ops:
            apply(w_dev, sg_dev, op)
            apply(w_host, sg_host, op)
        assert sg_dev.clock == sg_host.clock
        at_dev, at_host = sg_dev.query(), sg_host.query()

        # like-for-like bit-identity: delta vs delta and cold vs cold
        # (cold rebuilds order rows from post-GC slot order, delta keeps
        # history order — comparing across engines would need canon())
        pairs = [
            (eng_dev.snapshot(at_dev), eng_host.snapshot(at_host)),
            (SnapshotEngine(w_dev).snapshot(at_dev),
             SnapshotEngine(w_host).snapshot(at_host)),
        ]
        for got, want in pairs:
            assert got.vids[:got.n_nodes] == want.vids[:want.n_nodes]
            assert np.array_equal(got.edge_src, want.edge_src)
            assert np.array_equal(got.edge_dst, want.edge_dst)

    stats = w_dev.device_plane.stats
    assert stats["launches"] > 0, stats
    assert stats["rebuilds"] >= 1, stats
    assert compacted and stats["block_uploads"] > 0, stats
    assert eng_dev.stats["delta"] + eng_dev.stats["delta_noop"] > 0
    return stats
'''

_NS = {}
exec(CHURN_SRC, _NS)
_churn_equivalence = _NS["churn_equivalence"]


def run_sub(body: str, devices: int) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={devices}"
        import sys
        sys.path.insert(0, {os.path.join(REPO, 'src')!r})
        sys.path.insert(0, {REPO!r})
        import jax
        assert len(jax.devices()) == {devices}
    """) + CHURN_SRC + textwrap.dedent(body)
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\n" \
                                 f"STDERR:\n{proc.stderr[-3000:]}"
    return proc.stdout


class TestShardedEqualsHostGlobal:
    @pytest.mark.parametrize("seed", [0, 1])
    def test_single_device_churn(self, seed):
        """In-process coverage on the default 1-device CPU mesh."""
        stats = _churn_equivalence(seed, rounds=8)
        assert stats["launches"] >= 8

    @pytest.mark.parametrize("devices", [2, 4])
    def test_forced_multi_device_churn(self, devices):
        """Real multi-device shard_map: blocks distributed over forced
        host devices, masks still bit-identical to the host oracle."""
        out = run_sub(f"""
            import jax
            stats = churn_equivalence(0, rounds=6, n_chips={devices})
            # the mesh really had {devices} devices
            from repro.launch.mesh import make_columns_mesh
            assert make_columns_mesh().devices.size == {devices}
            print("DEVICE_SHARD_OK", stats)
        """, devices=devices)
        assert "DEVICE_SHARD_OK" in out

    def test_plane_defaults_to_one_chip(self):
        """A deployment that states no ``n_chips`` keeps its plane on one
        device however many are visible; one that states them spreads
        its shards' blocks over that many."""
        out = run_sub("""
            from repro.core import Weaver, WeaverConfig
            for chips in (None, 2, 4):
                kw = {} if chips is None else {"n_chips": chips}
                w = Weaver(WeaverConfig(n_gatekeepers=2, n_shards=8,
                                        device_shard_columns=True, **kw))
                tx = w.begin_tx()
                for i in range(40):
                    tx.create_vertex(f"n{i}")
                assert w.run_tx(tx).ok
                plane = w.device_plane
                plane.sync([sh.partition.columns for sh in w.shards])
                homes = {d.id for a in plane._dev for d in a.devices()}
                assert plane.mesh.size == plane.n_dev == (chips or 1)
                assert len(homes) == (chips or 1), homes
            print("ONE_CHIP_OK")
        """, devices=4)
        assert "ONE_CHIP_OK" in out

    def test_more_chips_than_devices_refused(self):
        """``n_chips`` past the visible devices, or not dividing
        ``n_shards``, fails at construction naming both counts."""
        out = run_sub("""
            from repro.core import Weaver, WeaverConfig
            for kw, words in (({"n_chips": 8}, ("n_chips=8", "4 devices")),
                              ({"n_chips": 4, "n_shards": 6},
                               ("n_shards=6", "n_chips=4"))):
                try:
                    Weaver(WeaverConfig(device_shard_columns=True, **kw))
                except ValueError as e:
                    assert all(w in str(e) for w in words), e
                else:
                    raise AssertionError(f"{kw} was accepted")
            print("REFUSED_OK")
        """, devices=4)
        assert "REFUSED_OK" in out

    def test_program_path_through_sharded_plans(self):
        """run_program (ShardPlan cold builds) agrees end-to-end through
        the real tx pipeline with sharding on vs off."""
        results = {}
        for flag in (False, True):
            from repro.core import Weaver, WeaverConfig
            w = Weaver(WeaverConfig(n_gatekeepers=2, n_shards=3, seed=11,
                                    device_shard_columns=flag))
            tx = w.begin_tx()
            for i in range(14):
                tx.create_vertex(f"n{i}")
            for i in range(13):
                tx.create_edge(f"n{i}", f"n{i+1}")
            assert w.run_tx(tx).ok
            tx = w.begin_tx()
            tx.delete_vertex("n7")
            assert w.run_tx(tx).ok
            r_reach, _, _ = w.run_program(
                "reachable", [("n0", {"target": "n13"})])
            r_count, _, _ = w.run_program("count_edges", [("n3", None)])
            results[flag] = (r_reach, r_count)
            if flag:
                assert w.device_plane.stats["launches"] > 0
        assert results[True] == results[False]

    def test_capacity_tracks_largest_partition(self):
        """Shards joining the plane one at a time (one ShardPlan build
        each) rebuild it at the size the largest partition needs, not
        at a multiple of the previous capacity."""
        from repro.core import Weaver, WeaverConfig
        from repro.kernels.backend import pow2

        w = Weaver(WeaverConfig(n_gatekeepers=2, n_shards=4, seed=3,
                                device_shard_columns=True))
        tx = w.begin_tx()
        for i in range(400):
            tx.create_vertex(f"n{i}")
        assert w.run_tx(tx).ok
        plane = w.device_plane
        cols = [sh.partition.columns for sh in w.shards]
        for c in cols:
            plane.sync([c])
        assert plane.stats["rebuilds"] == len(cols)
        need = max(max(c.n_v, c.n_e) for c in cols)
        assert plane._cap == pow2(max(plane.min_block, need + need // 4))


# The graph kernels split by destination range over the devices
# (analytics.pagerank_split / bfs_split) against the plain float64
# reference of the benchmark and against the one-device path.  The split
# path always runs the segment_sum kernels (interpreted on the CPU), so
# the one-device path is taken on its kernel path too.
SPLIT_SRC = '''
def rmat_csc(seed, scale, n):
    """A seeded R-MAT draw over 2**scale ids (edge factor 8, both
    directions), kept where both ends are below ``n``; CSC order."""
    import numpy as np
    rng = np.random.default_rng(seed)
    src = np.zeros(8 << scale, np.int64)
    dst = np.zeros_like(src)
    for _ in range(scale):
        q = rng.choice(4, src.size, p=[0.57, 0.19, 0.19, 0.05])
        src, dst = 2 * src + q // 2, 2 * dst + q % 2
    perm = rng.permutation(1 << scale)
    src, dst = perm[src], perm[dst]
    keep = (src < n) & (dst < n)
    src, dst = np.r_[src[keep], dst[keep]], np.r_[dst[keep], src[keep]]
    o = np.lexsort((src, dst))
    return src[o].astype(np.int32), dst[o].astype(np.int32)
'''


class TestSplitKernels:
    # n is no multiple of 4 * block_n (1024); "low" draws its ids below
    # 1024 of 1900 vertices, so the upper ranges receive no edges
    @pytest.mark.parametrize("case,scale,n", [("low", 10, 1900),
                                              ("spread", 12, 3000)])
    @pytest.mark.parametrize("devices", [2, 4])
    def test_split_matches_reference_and_one_device(self, devices, case,
                                                    scale, n):
        """PageRank within float32 rounding of the float64 reference
        (rtol 1e-5: 20 iterations of float32 sums, as for the one-device
        kernel path) and bit-equal to the one-device kernel path (each
        chip sums the same chunks in the same order); BFS levels equal to
        both.  The split's edges and slot room are as the layout says."""
        out = run_sub(SPLIT_SRC + textwrap.dedent(f"""
            import numpy as np
            import jax
            from bench.harness import reference
            from repro.core import analytics as A
            from repro.launch.mesh import make_columns_mesh

            n = {n}
            s, d = rmat_csc({devices}, {scale}, n)
            def ga(mesh):
                return A.GraphArrays(vids=list(range(n)), index={{}},
                                     edge_src=s, edge_dst=d, n_nodes=n,
                                     _csc=(s, d), mesh=mesh)
            A.FORCE_KERNEL = True
            one, split = ga(make_columns_mesh(1)), ga(make_columns_mesh())
            pr1 = np.asarray(A.pagerank_ga(one))
            pr = np.asarray(A.pagerank_ga(split))
            assert split._split is not None and one._split is None
            lay = split._split[0]
            assert lay.edges.sum() == s.size, lay.edges
            if "{case}" == "low":
                assert lay.edges[-1] == 0, lay.edges
            np.testing.assert_array_equal(pr.view(np.int32),
                                          pr1.view(np.int32))
            want = reference.pagerank(s, d, n, 20, 0.85)
            np.testing.assert_allclose(pr, want, rtol=1e-5)
            for root in (int(s[0]), int(d[-1]), n - 1):
                lv = np.asarray(A.bfs_levels_ga(split, [root]))
                np.testing.assert_array_equal(
                    lv, np.asarray(A.bfs_levels_ga(one, [root])))
                np.testing.assert_array_equal(
                    np.where(lv >= A.INF, -1, lv),
                    reference.bfs_levels(s, d, n, root))
            print("SPLIT_OK", lay.edges.tolist())
        """), devices=devices)
        assert "SPLIT_OK" in out

    def test_split_through_the_deployment(self):
        """A four-chip deployment's snapshot carries its plane's mesh;
        its PageRank and BFS take the split path, which the deployment's
        counters count and its host profile times as ``kernel_layout``,
        with the same results as the one-device deployment's."""
        out = run_sub("""
            import numpy as np
            from repro.core import Weaver, WeaverConfig, analytics as A
            from repro.core.obs import HostProfile

            res = {}
            for chips in (1, 4):
                w = Weaver(WeaverConfig(n_gatekeepers=2, n_shards=8,
                                        n_chips=chips, seed=5,
                                        device_shard_columns=True))
                rng = np.random.default_rng(5)
                tx = w.begin_tx()
                for i in range(300):
                    tx.create_vertex(f"v{i}")
                for a, b in rng.integers(0, 300, (1500, 2)).tolist():
                    tx.create_edge(f"v{a}", f"v{b}")
                assert w.run_tx(tx).ok
                box = []
                w.submit_program("get_node", [("v0", None)],
                                 lambda r, st, lat: box.append(st))
                w.sim.run(until=w.sim.now + 0.1)
                w.sim.host = HostProfile(w.sim)
                A.FORCE_KERNEL = True
                ga = A.SnapshotEngine(w).snapshot(box[0])
                assert ga.mesh is w.device_plane.mesh
                assert ga.mesh.size == chips
                res[chips] = (np.asarray(A.pagerank_ga(ga)),
                              np.asarray(A.bfs_levels_ga(ga, [ga.index["v0"]])),
                              ga.vids)
                c = w.counters()
                if chips == 1:
                    assert c["split_calls"] == 0
                    assert "kernel_layout" not in c["host_ns"]
                    continue
                e = ga.edge_src.size
                assert c["split_calls"] == 2 and c["split_chips"] == 8
                assert c["split_edge_slots"] == 2 * e
                assert e / 4 <= c["split_edge_slots_max"] / 2 <= e
                assert c["host_events"]["kernel_layout"] == 1
            assert res[1][2] == res[4][2]
            np.testing.assert_array_equal(res[1][0], res[4][0])
            np.testing.assert_array_equal(res[1][1], res[4][1])
            print("DEPLOYMENT_SPLIT_OK")
        """, devices=4)
        assert "DEPLOYMENT_SPLIT_OK" in out
