"""Deployment-wide observability: causal spans, the host wall-clock
profile, metrics timeline, critical-path attribution, trace invariants,
and the two ride-along optimisations (shared AIMD load signal,
read-window aliasing + clustering wire dedup).

The load-bearing property throughout: tracing is *pure observation*.
A traced or host-profiled run and an untraced run of the same seeded
workload must be bit-identical in results and in every counter except
the obs tallies themselves (``OBS_COUNTER_FIELDS``).
"""

import heapq
import json
import os
import re
import time
from types import SimpleNamespace

import pytest

from repro.core import Weaver, WeaverConfig
from repro.core import analytics
from repro.core.faultinject import FaultPlan
from repro.core.obs import (HOST_STAGES, OBS_COUNTER_FIELDS, ROOT_STAGES,
                            HostProfile, attribution_table,
                            check_completeness, check_replica_staleness,
                            export_trace, run_invariant_checks,
                            validate_trace_events)
from repro.core.simulation import Counters

DOCS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "docs", "OBSERVABILITY.md")


def _tx_read_workload(rate: float, seed: int = 11, host: bool = False):
    """The equivalence workload: adaptive admission, bounded queues,
    retry sessions — enough machinery that any tracing side effect
    (an extra event, an RNG draw) would shift results or counters."""
    cfg = WeaverConfig(trace_sample_rate=rate, write_group_commit=1e-3,
                       read_group_commit=1e-3, adaptive_admission=True,
                       admission_queue_limit=8, read_retry_timeout=4e-3,
                       seed=seed)
    w = Weaver(cfg)
    if host:
        w.sim.host = HostProfile(w.sim)
    results = []
    for i in range(30):
        tx = w.begin_tx()
        tx.create_vertex(f"v{i}")
        if i:
            tx.create_edge(f"v{i - 1}", f"v{i}")
        r = w.run_tx(tx)
        results.append((r.ok, round(r.latency, 12)))
    for i in range(10):
        res = w.run_program("count_edges", [(f"v{i}", None)])
        results.append((res[0], round(res[2], 12)))
    w.settle()
    return w, results


#: the observed side of each purity case: (trace_sample_rate, host
#: profile installed); the other side is (0.0, False)
OBSERVED = {"tracer-0.5": (0.5, False), "tracer-1.0": (1.0, False),
            "host": (0.0, True), "both": (1.0, True)}


class TestPureObservation:
    @pytest.mark.parametrize("mode", list(OBSERVED))
    def test_tracing_changes_nothing(self, mode):
        """Results and non-obs counters are bit-identical with the
        tracer, the host profile, or both, against neither."""
        runs = []
        for rate, host in ((0.0, False), OBSERVED[mode]):
            w, results = _tx_read_workload(rate, host=host)
            c = w.counters()
            for f in OBS_COUNTER_FIELDS:
                c.pop(f, None)
            runs.append((results, c))
        (base_res, base_c), (res, c) = runs
        assert res == base_res, f"{mode} changed results"
        diff = {k: (base_c.get(k), c.get(k))
                for k in set(base_c) | set(c)
                if base_c.get(k) != c.get(k)}
        assert not diff, f"{mode} changed counters: {diff}"

    def test_disabled_tracer_records_nothing(self):
        w, _ = _tx_read_workload(0.0)
        assert w.sim.tracer is None
        assert w.sim.counters.spans_recorded == 0

    def test_host_profile_off_records_nothing(self):
        """The simulator follows the JAX profiler: with none capturing,
        no profile is installed and nothing is recorded."""
        w, _ = _tx_read_workload(0.0)
        assert w.sim.host is None
        assert w.counters()["host_ns"] == {}
        assert w.counters()["host_events"] == {}


def _doc_host_stages() -> dict:
    """Stage -> layer rows of OBSERVABILITY.md's host-profile table."""
    with open(DOCS) as f:
        text = f.read()
    sec = text.split("## Host wall-clock profile", 1)[1].split("\n## ", 1)[0]
    return dict(re.findall(r"^\| `([a-z_]+)` \| [^|]+ \| ([^|]+?) \|",
                           sec, re.M))


def _host_workload(**kw):
    """Writes, reads, GC and two snapshots (cold, then delta) through the
    device column plane, with the host profile on."""
    cfg = WeaverConfig(write_group_commit=1e-3, read_group_commit=1e-3,
                       device_shard_columns=True, gc_period=5e-3, seed=21,
                       **kw)
    w = Weaver(cfg)
    w.sim.host = HostProfile(w.sim)
    tx = w.begin_tx()
    for i in range(12):
        tx.create_vertex(f"h{i}")
    for i in range(11):
        tx.create_edge(f"h{i}", f"h{i + 1}")
    assert w.run_tx(tx).ok
    return w


class TestHostProfile:
    def test_nested_spans_give_self_time(self):
        sim = SimpleNamespace(counters=Counters())
        h = HostProfile(sim)

        def handler():
            time.sleep(0.002)
            with h.span("inner"):
                time.sleep(0.003)
            assert h.call("leaf", lambda x: x + 1, 1) == 2

        t0 = time.perf_counter_ns()
        t1 = h.event(handler, (), t0)      # a root span from t0
        ns = sim.counters.host_ns
        assert ns["inner"] >= 3_000_000
        assert ns["other"] >= 2_000_000
        # the children's time is the parent's no more: the stages tile
        # the root span exactly
        assert ns["other"] + ns["inner"] + ns["leaf"] == t1 - t0
        assert sim.counters.host_events == {"other": 1, "inner": 1,
                                            "leaf": 1}
        with pytest.raises(ZeroDivisionError):
            h.call("boom", lambda: 1 / 0)
        assert h._stack == [] and sim.counters.host_events["boom"] == 1

    def test_every_event_one_root_stage_and_wall_covered(self, monkeypatch):
        w = _host_workload()
        sim = w.sim
        done = []
        for i in range(10):
            tx = w.begin_tx()
            tx.create_edge(f"h{i}", f"h{(i + 5) % 12}")
            w.submit_tx(tx, done.append)
            w.submit_program("count_edges", [(f"h{i}", None)],
                             lambda r, s, l: done.append(r))
        pops = [0]
        real = heapq.heappop

        def counting(heap):
            if heap is sim._heap:
                pops[0] += 1
            return real(heap)
        monkeypatch.setattr(heapq, "heappop", counting)
        ns0 = dict(sim.counters.host_ns)
        ev0 = dict(sim.counters.host_events)
        t0 = time.perf_counter_ns()
        sim.run(until=sim.now + 30e-3)
        wall = time.perf_counter_ns() - t0
        monkeypatch.undo()
        assert len(done) == 20
        c = sim.counters
        ev = {k: v - ev0.get(k, 0) for k, v in c.host_events.items()}
        ns = {k: v - ns0.get(k, 0) for k, v in c.host_ns.items()}
        roots = set(ROOT_STAGES.values()) | {"other"}
        assert pops[0] > 1000
        assert sum(n for k, n in ev.items() if k in roots) == pops[0]
        assert abs(sum(ns.values()) - wall) <= 0.02 * wall, (ns, wall)

    def test_every_stage_is_documented(self):
        w = _host_workload(n_replicas=1)
        for i in range(4):
            w.run_program("count_edges", [(f"h{i}", None)])
        w.settle(20e-3)
        eng = analytics.SnapshotEngine(w)
        at = w.run_program("get_node", [("h0", None)])[1]
        eng.snapshot(at)
        tx = w.begin_tx()
        tx.create_edge("h3", "h9")
        assert w.run_tx(tx).ok
        eng.snapshot(w.run_program("get_node", [("h0", None)])[1])
        assert eng.stats["cold"] == 1 and eng.stats["delta"] == 1
        doc = _doc_host_stages()
        assert doc == HOST_STAGES
        assert set(ROOT_STAGES.values()) <= set(HOST_STAGES)
        seen = set(w.counters()["host_ns"])
        assert seen <= set(HOST_STAGES), seen - set(HOST_STAGES)
        assert {"gk_stamp", "gk_nop", "shard_order", "shard_nop",
                "frontier_hop", "store_commit", "wal_append", "gc",
                "snapshot_cold", "snapshot_delta", "csr_compact",
                "plane_sync", "plane_launch"} <= seen, seen

    def test_follows_the_jax_profiler(self, tmp_path):
        """An annotating profile exists while a profiler trace is
        captured, and its events' stages land on the host plane as
        ``weaver.<stage>``."""
        import jax

        w, _ = _tx_read_workload(0.0)
        assert w.sim.host is None
        with jax.profiler.trace(str(tmp_path)):
            for i in range(3):
                w.run_program("count_edges", [(f"v{i}", None)])
            assert w.sim.host is not None and w.sim.host.annotate
        w.settle(1e-3)
        assert w.sim.host is None
        assert w.counters()["host_ns"].get("shard", 0) > 0
        path = next(os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
                    for f in fs if f.endswith(".xplane.pb"))
        pd = jax.profiler.ProfileData.from_file(path)
        names = {e.name for pl in pd.planes for ln in pl.lines
                 for e in ln.events}
        assert {"weaver.shard", "weaver.gk_stamp"} <= names, names
        # inside an event only the root is annotated; the nested stages
        # are counted
        assert "weaver.shard_order" not in names
        assert w.counters()["host_ns"].get("shard_order", 0) > 0


class TestAttribution:
    def test_stage_sums_match_e2e(self):
        """The critical-path analyzer tiles every sampled request's
        root exactly: per-request stage sums equal measured e2e."""
        w, _ = _tx_read_workload(1.0)
        tr = w.sim.tracer
        assert tr.spans and len(tr.traces()) >= 10
        attr = attribution_table(tr)
        rows = [r for r in attr["requests"] if "e2e" in r]
        assert rows, "no complete traces to attribute"
        assert attr["max_rel_err"] < 0.01, attr["max_rel_err"]
        for r in rows:
            assert abs(sum(r["stages"].values()) - r["e2e"]) \
                <= 0.01 * max(r["e2e"], 1e-12)
        # the stage taxonomy actually shows up (not everything "network")
        stages = set(attr["stages"])
        assert {"gk_stamp", "store_commit"} <= stages, stages

    def test_chrome_trace_export(self, tmp_path):
        w, _ = _tx_read_workload(1.0)
        path = tmp_path / "trace.json"
        doc = export_trace(w.sim.tracer, str(path))
        assert validate_trace_events(doc) == []
        on_disk = json.loads(path.read_text())
        assert validate_trace_events(on_disk) == []
        evs = on_disk["traceEvents"]
        assert any(e["ph"] == "X" for e in evs)
        assert any(e["ph"] == "M" and e["name"] == "thread_name"
                   for e in evs)
        # counter parity: every span became exactly one complete event
        assert sum(e["ph"] == "X" for e in evs) \
            == w.sim.counters.spans_recorded == len(w.sim.tracer.spans)


class TestMetricsTimeline:
    def test_periodic_sampling_and_hists(self):
        cfg = WeaverConfig(write_group_commit=1e-3, read_group_commit=1e-3,
                           adaptive_admission=True, metrics_period=2e-3,
                           seed=13)
        w = Weaver(cfg)
        for i in range(20):
            tx = w.begin_tx()
            tx.create_vertex(f"m{i}")
            assert w.run_tx(tx).ok
        for i in range(8):
            w.run_program("get_node", [(f"m{i}", None)])
        w.settle()
        m = w.sim.metrics
        assert w.sim.counters.metrics_samples > 0
        out = m.export()
        assert out["timeline"], "periodic timer never sampled"
        # gauges carry the shared load + queue depth signals
        names = {k for s in out["timeline"] for k in s}
        assert any(k.startswith("gk_admitted:") for k in names), names
        assert any(k.startswith("shard_queue:") for k in names), names
        # admission histograms moved off the ad-hoc Counters lists
        assert m.hists.get("admission_window_us_w") \
            or m.hists.get("admission_window_us_r")
        c = w.counters()
        assert "admission_window_hist" not in c
        assert "admission_depth_hist" not in c


class TestTraceInvariantsUnderFaults:
    """Chaos schedules from the fault-injection harness: every sampled
    request still yields a complete, invariant-clean trace."""

    @pytest.mark.parametrize("chaos_seed", [0, 2, 4])
    def test_complete_and_invariant_clean(self, chaos_seed):
        plan = FaultPlan.random(chaos_seed, n_gk=2, n_shards=3)
        cfg = WeaverConfig(n_gatekeepers=2, n_shards=3, seed=7,
                           write_group_commit=0.5e-3,
                           trace_sample_rate=1.0, fault_plan=plan)
        w = Weaver(cfg)
        w.sim.fault.disarm()           # fault-free setup traffic
        tx = w.begin_tx()
        tx.create_vertex("hub")
        assert w.run_tx(tx).ok
        w.sim.fault.arm()
        results = {}
        for i in range(24):
            v = f"x{i}"
            tx = w.begin_tx()
            tx.create_vertex(v)
            tx.create_edge(v, "hub")
            w.submit_tx(tx, lambda r, v=v: results.__setitem__(v, r))
        w.settle(2.0)
        w.sim.fault.disarm()
        assert len(results) == 24, "a client session hung"

        tr = w.sim.tracer
        assert check_completeness(tr) == []
        checks = run_invariant_checks(tr)
        for name, findings in checks.items():
            assert findings == [], (chaos_seed, name, findings[:5])
        # attribution still tiles the completed requests
        attr = attribution_table(tr)
        assert attr["max_rel_err"] < 0.01


class TestReplicaStalenessInvariant:
    """The replica-staleness checker over fault-injected replicated
    traces: no read may be served by a replica whose applied frontier
    is behind the stamp's settlement token."""

    @pytest.mark.parametrize("chaos_seed", [1, 3])
    def test_replicated_chaos_traces_clean(self, chaos_seed):
        plan = FaultPlan.random(chaos_seed, n_gk=2, n_shards=3,
                                n_crashes=0, replica_faults=True)
        cfg = WeaverConfig(n_gatekeepers=2, n_shards=3, n_replicas=2,
                           seed=7, trace_sample_rate=1.0,
                           read_group_commit=1e-3, fault_plan=plan)
        w = Weaver(cfg)
        w.sim.fault.disarm()
        tx = w.begin_tx()
        for i in range(8):
            tx.create_vertex(f"v{i}")
        for i in range(7):
            tx.create_edge(f"v{i}", f"v{i+1}")
        assert w.run_tx(tx).ok
        w.settle(50e-3)
        w.sim.fault.arm()
        for i in range(16):
            w.run_program("count_edges", [(f"v{i % 8}", None)])
            w.settle(2e-3)
        w.sim.fault.disarm()
        w.settle(0.2)
        tr = w.sim.tracer
        served = [s for s in tr.spans if s.stage == "replica_read"]
        assert served, "no replica-served reads to check"
        checks = run_invariant_checks(tr)
        for name, findings in checks.items():
            assert findings == [], (chaos_seed, name, findings[:5])

    def test_checker_flags_fabricated_violations(self):
        """Negative control: a hand-built stale replica_read span is
        reported by the checker (both failure shapes)."""
        cfg = WeaverConfig(trace_sample_rate=1.0, seed=3)
        w = Weaver(cfg)
        tx = w.begin_tx()
        tx.create_vertex("a")
        assert w.run_tx(tx).ok
        tr = w.sim.tracer
        assert check_replica_staleness(tr) == []
        ctx = (tr.spans[0].trace, tr.spans[0].sid)
        tr.span("replica_read", 0.0, 0.0, actor="shard0r0", ctx=ctx,
                shard=0, replica=0, settle_pos=7, applied_pos=3)
        tr.span("replica_read", 0.0, 0.0, actor="shard1r1", ctx=ctx,
                shard=1, replica=1, settle_pos=-1, applied_pos=0)
        errs = check_replica_staleness(tr)
        assert len(errs) == 2, errs
        assert any("behind settle_pos" in e for e in errs)
        assert any("without a settlement token" in e for e in errs)


class TestSharedLoadSignal:
    def _hammer(self, shared: bool):
        cfg = WeaverConfig(write_group_commit=1e-3, adaptive_admission=True,
                           admission_queue_limit=4, shed_nack=True,
                           shared_load_signal=shared, seed=4)
        w = Weaver(cfg)
        done = []
        # all load on gk0: it saturates and sheds; with the shared
        # signal its peers (serving the NACK reroutes) see the
        # deployment-level pressure and grow their windows
        for i in range(60):
            tx = w.begin_tx()
            tx.create_vertex(f"x{i}")
            w.submit_tx(tx, done.append, gatekeeper=0)
        while len(done) < 60 and w.sim.pending():
            w.sim.run(until=w.sim.now + 5e-3)
        return done, w.counters()

    def test_peer_load_grows_windows(self):
        done_off, c_off = self._hammer(False)
        done_on, c_on = self._hammer(True)
        assert sum(r.ok for r in done_off) == 60
        assert sum(r.ok for r in done_on) == 60
        assert c_off["window_grows_shared"] == 0
        assert c_on["window_grows_shared"] > 0, c_on


class TestReadWindowAliasing:
    def _reads(self, alias: bool):
        cfg = WeaverConfig(read_group_commit=1e-3, read_window_alias=alias,
                           seed=5)
        w = Weaver(cfg)
        tx = w.begin_tx()
        tx.create_vertex("a")
        tx.create_vertex("b")
        tx.create_edge("a", "b")
        assert w.run_tx(tx).ok
        out = [w.run_program("count_edges", [("a", None)])[0]
               for _ in range(6)]
        return out, w.sim.counters.read_windows_aliased

    def test_quiescent_reads_alias(self):
        res_on, aliased_on = self._reads(True)
        res_off, aliased_off = self._reads(False)
        assert aliased_on > 0 and aliased_off == 0
        assert res_on == res_off == [1] * 6

    def test_write_invalidates_alias(self):
        """A mutation between read windows must bump the seqno and
        force a fresh stamp — the next read sees the write."""
        cfg = WeaverConfig(read_group_commit=1e-3, seed=5)
        w = Weaver(cfg)
        tx = w.begin_tx()
        tx.create_vertex("a")
        tx.create_vertex("b")
        tx.create_edge("a", "b")
        assert w.run_tx(tx).ok
        assert w.run_program("count_edges", [("a", None)])[0] == 1
        tx = w.begin_tx()
        tx.create_vertex("c")
        tx.create_edge("a", "c")
        assert w.run_tx(tx).ok
        assert w.run_program("count_edges", [("a", None)])[0] == 2


class TestClusteringWireDedup:
    def _clique_run(self, alias: bool):
        """Dense 14-clique; repeated clustering queries pinned to gk0 so
        they share stamps (the round-robin router would otherwise split
        them across gatekeepers and defeat the same-stamp cache)."""
        cfg = WeaverConfig(read_group_commit=2e-3, read_window_alias=alias,
                           seed=7)
        w = Weaver(cfg)
        tx = w.begin_tx()
        N = 14
        for i in range(N):
            tx.create_vertex(f"k{i}")
        for i in range(N):
            for j in range(N):
                if i != j:
                    tx.create_edge(f"k{i}", f"k{j}")
        assert w.run_tx(tx).ok
        done = []
        for _ in range(2):
            w.submit_program("clustering", [("k0", {"phase": 0})],
                             lambda r, s, l: done.append(r), gatekeeper=0)
        while len(done) < 2 and w.sim.pending():
            w.sim.run(until=w.sim.now + 5e-3)
        done2 = []
        w.submit_program("clustering", [("k0", {"phase": 0})],
                         lambda r, s, l: done2.append(r), gatekeeper=0)
        while len(done2) < 1 and w.sim.pending():
            w.sim.run(until=w.sim.now + 5e-3)
        return (done + done2, w.sim.counters.bytes_sent,
                w.sim.counters.nbr_rows_cached)

    def test_bytes_regression(self):
        res_on, bytes_on, cached_on = self._clique_run(alias=True)
        res_off, bytes_off, cached_off = self._clique_run(alias=False)
        assert res_on == res_off, "dedup changed clustering results"
        assert all(r == pytest.approx(1.0) for r in res_on), res_on
        assert cached_on > cached_off > 0, (cached_on, cached_off)
        assert bytes_on < bytes_off, \
            f"aliased windows shipped no fewer bytes ({bytes_on} vs " \
            f"{bytes_off})"
