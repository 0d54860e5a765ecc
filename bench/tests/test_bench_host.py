"""The per-layer metrics that read the program's host profile
(``Counters.host_ns``), on synthetic run records: each gives its stages'
self time per request, and none reads anything from a program or a run
without the profile."""

import pytest

from bench.harness import spec
from bench.harness.cell import RunRecord, Spans

NS0 = {"gatekeeper": 1_000, "shard": 5_000}
NS1 = {"gatekeeper": 3_001_000, "gk_stamp": 2_000_000, "gk_nop": 1_000_000,
       "oracle": 500_000,
       "store_commit": 400_000, "wal_append": 100_000, "store_gc": 50_000,
       "shard": 4_005_000, "shard_order": 9_000_000, "shard_apply": 700_000,
       "shard_nop": 300_000, "shard_gc": 2_000_000,
       "frontier_hop": 1_500_000, "plan_build": 600_000,
       "plan_refresh": 400_000, "coordinator": 250_000,
       "plane_sync": 120_000, "plane_scatter": 3_000_000,
       "plane_launch": 880_000, "client": 7_000_000}


def _rec(c0, c1, latency=(0.1, None, 0.2, 0.3), snapshots=2):
    spans = Spans(False)
    spans.count["snapshot"] = snapshots
    return RunRecord(cell="x", kinds=["get_node"] * len(latency),
                     latency_s=list(latency), counters0=c0, counters1=c1,
                     spans=spans)


# (metric, expected: self seconds of its stages * scale / 3 completed)
CASES = [
    ("gatekeeper_host_us.tao", (3.0e-3 + 2e-3 + 1e-3 + 0.5e-3) * 1e6 / 3),
    ("store_host_us.tao", (0.4e-3 + 0.1e-3 + 0.05e-3) * 1e6 / 3),
    ("shard_host_us.tao",
     (4e-3 + 9e-3 + 0.7e-3 + 0.3e-3 + 2e-3) * 1e6 / 3),
    ("frontier_host_us.tao",
     (1.5e-3 + 0.6e-3 + 0.4e-3 + 0.25e-3) * 1e6 / 3),
    ("commit_host_ms.analytics",
     (6.5e-3 + 0.55e-3 + 16e-3) * 1e3 / 3),
    ("plan_host_ms.analytics", (0.6e-3 + 0.4e-3) * 1e3 / 3),
]


@pytest.mark.parametrize("name,want", CASES)
def test_reader_on_synthetic_record(name, want):
    read = spec.reader(name)
    assert read(_rec({"host_ns": NS0}, {"host_ns": NS1})) == \
        pytest.approx(want)
    # a traced run's counters0 before the profile was installed
    assert read(_rec({"host_ns": {}}, {"host_ns": NS1})) > 0


def test_plane_sync_per_snapshot():
    read = spec.reader("plane_sync_ms.analytics")
    rec = _rec({"host_ns": {}}, {"host_ns": NS1}, snapshots=2)
    assert read(rec) == pytest.approx((0.12e-3 + 3e-3 + 0.88e-3) * 1e3 / 2)
    assert read(_rec({"host_ns": {}}, {"host_ns": NS1}, snapshots=0)) is None


@pytest.mark.parametrize("name", [c[0] for c in CASES]
                         + ["plane_sync_ms.analytics"])
def test_nothing_to_read(name):
    """A program without the profile (no ``host_ns``), a run without the
    profiler (empty), or no completed request: the metric is left out."""
    read = spec.reader(name)
    assert read(_rec({}, {})) is None
    assert read(_rec({"host_ns": {}}, {"host_ns": {}})) is None
    assert read(_rec({"host_ns": NS0}, {"host_ns": NS1},
                     latency=(None, None), snapshots=0)) is None


def test_metrics_are_declared_for_their_cells():
    tao = spec.resolve("social-lj.tao")
    an = spec.resolve("graph500-s15.analytics")
    names_tao = {m.name for m in tao.per_layer}
    names_an = {m.name for m in an.per_layer}
    assert {c[0] for c in CASES if c[0].endswith(".tao")} <= names_tao
    assert {"commit_host_ms.analytics", "plan_host_ms.analytics",
            "plane_sync_ms.analytics"} <= names_an
    assert not any(n.endswith(".tao") for n in names_an)
