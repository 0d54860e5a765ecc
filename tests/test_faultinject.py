"""Deterministic fault injection + chaos property test (ISSUE 6 tentpole c).

Every named crash point (``repro.core.faultinject.CRASH_POINTS``) must
leave the cluster in a state where (a) every client submission resolves
— commit, abort, or a surfaced budget-exhaustion error, never a hang —
(b) every ACKED transaction's effects survive recovery, and (c) no
transaction commits twice (a duplicate ``create_vertex`` would abort
with "exists", so its absence doubles as the double-commit detector).

The chaos test draws randomized kill schedules from
:meth:`FaultPlan.random` and checks the same invariants, comparing the
surviving state against a fault-free run of the identical workload.
"""

import numpy as np
import pytest

from repro.core import Weaver, WeaverConfig
from repro.core.faultinject import FaultAction, FaultPlan

from test_recovery import assert_replay_equals_walk


def make_weaver(plan=None, **kw):
    kw.setdefault("n_gatekeepers", 2)
    kw.setdefault("n_shards", 3)
    kw.setdefault("seed", 7)
    return Weaver(WeaverConfig(fault_plan=plan, **kw))


def seed_hub(w):
    """Fault-free setup traffic (callers disarm the injector first)."""
    tx = w.begin_tx()
    tx.create_vertex("hub")
    assert w.run_tx(tx).ok


def submit_unique(w, i, results):
    """One self-contained tx on a unique key; result lands in
    ``results[vid]`` whenever the session resolves it."""
    v = f"x{i}"
    tx = w.begin_tx()
    tx.create_vertex(v)
    tx.create_edge(v, "hub")
    tx.set_vertex_prop(v, "score", float(i))
    w.submit_tx(tx, lambda r, v=v: results.__setitem__(v, r))
    return v


def check_acked_visible(w, results):
    """Every acked tx is in the store AND at its (live) shard replica;
    no tx re-executed (no "exists" abort)."""
    assert not any("exists" in (r.error or "")
                   for r in results.values()), "a committed tx re-executed"
    acked = [v for v, r in results.items() if r.ok]
    for v in acked:
        sv = w.store.vertices.get(v)
        assert sv is not None and sv.delete_ts is None, f"acked {v} lost"
        assert sv.props["score"][-1][0] == float(v[1:])
        assert any(dst == "hub" and dts is None
                   for dst, _, dts in sv.edges.values()), f"{v} edge lost"
        sh = w.shards[w.store.place(v)]
        if sh.alive:
            assert v in sh.partition.vertices, f"acked {v} missing at shard"
    return acked


class TestCrashPoints:
    # mid_window occurs once per admitted tx (skip one), pre/post_wal
    # once per committed window (fire on the first)
    @pytest.mark.parametrize("point,after", [("mid_window", 1),
                                             ("pre_wal", 0),
                                             ("post_wal", 0)])
    def test_gatekeeper_crash_point(self, point, after):
        plan = FaultPlan([FaultAction("crash", point=point, target="gk0",
                                      after=after)])
        w = make_weaver(plan, write_group_commit=0.5e-3)
        w.sim.fault.disarm()
        seed_hub(w)
        w.sim.fault.arm()
        results = {}
        for i in range(12):
            submit_unique(w, i, results)
        w.settle(1.0)
        c = w.sim.counters
        assert c.crashes_injected == 1
        assert len(results) == 12, "a client session hung"
        acked = check_acked_visible(w, results)
        assert len(acked) == 12, "a lost tx was never retried to success"
        if point == "mid_window":
            # the admitted-but-unflushed window is counted, not silent
            assert c.group_txs_lost > 0
        if point == "post_wal":
            # classic lost ack: durable commit, dead server — the
            # resubmission must answer from the recorded outcome
            assert c.tx_dedup_hits >= 1
            assert any(r.retries > 0 for r in results.values())

    def test_mid_wal_torn_tail(self):
        """The store's group append is cut short: the torn entries are
        on the log but never acked; clients re-drive them to the
        survivor and replay truncates the tail."""
        plan = FaultPlan([FaultAction("torn", point="mid_wal", target="gk0",
                                      after=0, arg=1)])
        w = make_weaver(plan, write_group_commit=0.5e-3)
        w.sim.fault.disarm()
        seed_hub(w)
        w.sim.fault.arm()
        results = {}
        for i in range(10):
            submit_unique(w, i, results)
        w.settle(1.0)
        assert w.sim.counters.crashes_injected == 1
        assert len(results) == 10
        acked = check_acked_visible(w, results)
        assert len(acked) == 10
        # replay across the torn record truncates (and agrees with the walk)
        torn0 = w.sim.counters.wal_torn_truncated
        assert_replay_equals_walk(w)
        assert w.sim.counters.wal_torn_truncated > torn0

    def test_mid_shard_apply(self):
        # vertex->shard placement follows the per-process string hash;
        # target the shard that receives the most tx applies so the
        # after=2 crash point always fires (a fixed "shard1" is flaky
        # under unlucky PYTHONHASHSEED draws — 12 txs over 3 shards can
        # leave it with fewer than 3 items)
        probe = make_weaver()
        counts = [0] * len(probe.shards)
        for i in range(12):
            counts[probe.store.place(f"x{i}")] += 1
        plan = FaultPlan([FaultAction("crash", point="mid_shard_apply",
                                      target=f"shard{int(np.argmax(counts))}",
                                      after=2)])
        w = make_weaver(plan)
        w.sim.fault.disarm()
        seed_hub(w)
        w.sim.fault.arm()
        results = {}
        for i in range(12):
            submit_unique(w, i, results)
        w.settle(1.0)
        assert w.sim.counters.crashes_injected == 1
        assert w.manager.epoch >= 1, "shard death never promoted"
        assert len(results) == 12
        acked = check_acked_visible(w, results)
        assert len(acked) == 12
        assert_replay_equals_walk(w)

    def test_epoch_barrier_second_failure(self):
        """A second actor dies INSIDE the epoch barrier commit; the next
        heartbeat check promotes it in a follow-up epoch."""
        plan = FaultPlan([FaultAction("crash", point="epoch_barrier",
                                      target="shard2")])
        w = make_weaver(plan)
        w.sim.fault.disarm()
        seed_hub(w)
        results = {}
        for i in range(6):
            submit_unique(w, i, results)
        w.settle(20e-3)
        w.sim.fault.arm()
        w.kill("gk0")                    # first failure triggers the barrier
        w.settle(1.0)
        c = w.sim.counters
        assert c.crashes_injected == 1
        assert w.manager.epoch >= 2, "barrier victim never re-promoted"
        assert all(sh.alive for sh in w.shards)
        for i in range(6, 12):
            submit_unique(w, i, results)
        w.settle(1.0)
        assert len(results) == 12
        acked = check_acked_visible(w, results)
        assert len(acked) == 12


class TestClientSession:
    def test_retry_budget_exhausted_surfaces_error(self):
        """With every gatekeeper dead and promotion disabled, the
        bounded retry budget surfaces an error instead of hanging."""
        w = make_weaver(heartbeat_period=10.0)
        seed_hub(w)
        w.kill("gk0")
        w.kill("gk1")
        results = {}
        submit_unique(w, 0, results)
        w.settle(1.5)
        r = results["x0"]
        assert not r.ok
        assert r.error == "client retry budget exhausted"
        assert r.retries == w.cfg.client_retry_budget
        assert w.sim.counters.client_gaveup == 1

    def test_message_faults_counted_and_survived(self):
        """Dropped acks are re-asked (dedup answers), duplicated
        submissions are consumed by the in-flight gate, delays just
        add latency — and each is tallied."""
        plan = FaultPlan([
            FaultAction("drop", target="reply", after=0, count=2),
            FaultAction("dup", target="submit_tx", after=1, count=2),
            FaultAction("delay", target="reply", after=4, count=3,
                        delay=2e-3),
        ])
        w = make_weaver(plan)
        w.sim.fault.disarm()
        seed_hub(w)
        w.sim.fault.arm()
        results = {}
        for i in range(10):
            submit_unique(w, i, results)
        w.settle(1.0)
        c = w.sim.counters
        assert c.msgs_dropped >= 1
        assert c.msgs_duplicated >= 1
        assert c.msgs_delayed >= 1
        assert len(results) == 10
        acked = check_acked_visible(w, results)
        assert len(acked) == 10


class TestExactlyOnce:
    @pytest.mark.parametrize("group", [0.0, 0.5e-3])
    @pytest.mark.parametrize("overlap", [False, True])
    def test_internal_retry_and_resubmission_commit_once(self, group,
                                                        overlap):
        """A gatekeeper's internal retry (``retries > 0``) of a txid
        that a client resubmission, past the store's stale in-flight
        claim, also carries commits once: whichever attempt comes second
        is answered from the recorded outcome, whether it reaches the
        gate after the first committed or while both are in flight."""
        w = make_weaver(write_group_commit=group)
        seed_hub(w)
        tx = w.begin_tx()
        tx.create_vertex("x0")
        tx.create_edge("x0", "hub")
        replies = []
        c = w.sim.counters
        committed = c.tx_committed

        def attempt(gk, retries):
            args = (w, tx.ops, gk._tick(),
                    lambda ok, err, stamp: replies.append((ok, err)),
                    retries, w.sim.now, "dup-tx")
            if group:
                gk._at_store_batch([args])
            else:
                gk._at_store(*args)

        attempt(w.gatekeepers[0], 0)     # the resubmission
        if not overlap:
            w.settle(0.05)
        attempt(w.gatekeepers[1], 1)     # the older attempt's retry
        w.settle(0.05)
        assert replies == [(True, None), (True, None)]
        assert c.tx_committed - committed == 1
        assert c.tx_dedup_hits == 1
        assert w.store.tx_results["dup-tx"][0] is True
        assert len(w.store.vertices["x0"].edges) == 1


class TestChaosProperty:
    """Randomized kill schedules: every acked tx survives recovery and
    the surviving state matches the fault-free run of the same workload
    on the acked prefix (fixed seeds keep this tier-1 deterministic)."""

    N = 24

    def _run(self, plan, **kw):
        w = make_weaver(plan, write_group_commit=0.5e-3, **kw)
        if w.sim.fault is not None:
            w.sim.fault.disarm()
        seed_hub(w)
        if w.sim.fault is not None:
            w.sim.fault.arm()
        results = {}
        for i in range(self.N):
            submit_unique(w, i, results)
        w.settle(2.0)
        if w.sim.fault is not None:
            w.sim.fault.disarm()         # verification traffic is fault-free
        return w, results

    @pytest.mark.parametrize("chaos_seed", [0, 1, 2, 3, 4, 5])
    def test_acked_txs_survive_randomized_faults(self, chaos_seed):
        ref, ref_results = self._run(None)
        assert all(r.ok for r in ref_results.values())

        plan = FaultPlan.random(chaos_seed, n_gk=2, n_shards=3)
        w, results = self._run(plan)
        assert len(results) == self.N, "a client session hung"
        acked = check_acked_visible(w, results)
        # only a surfaced budget error may stand between a client and an ack
        for v, r in results.items():
            if not r.ok:
                assert r.error == "client retry budget exhausted", \
                    f"{v}: unexplained abort {r.error!r}"
        # acked state == the fault-free run's committed prefix
        for v in acked:
            sv, rv = w.store.vertices[v], ref.store.vertices[v]
            assert sv.props["score"][-1][0] == rv.props["score"][-1][0]
            assert sorted(dst for dst, _, dts in sv.edges.values()
                          if dts is None) == \
                sorted(dst for dst, _, dts in rv.edges.values()
                       if dts is None)
        # both recovery paths still agree after the dust settles
        assert_replay_equals_walk(w)
