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
def churn_equivalence(seed, rounds=10, forced_compaction_round=5):
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
        return Weaver(WeaverConfig(n_gatekeepers=2, n_shards=3,
                                   gc_period=0, seed=seed,
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
            stats = churn_equivalence(0, rounds=6)
            # the mesh really had {devices} devices
            from repro.launch.mesh import make_columns_mesh
            assert make_columns_mesh().devices.size == {devices}
            print("DEVICE_SHARD_OK", stats)
        """, devices=devices)
        assert "DEVICE_SHARD_OK" in out

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
