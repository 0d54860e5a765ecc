"""Percentiles over every request and rates over the whole window, on
synthetic latencies, through the metric readers themselves."""

import statistics

import pytest

from bench.harness import spec, stats
from bench.harness.cell import RunRecord, Spans


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 100) == 100
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([3, 1, 2], 50) == 2
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_spread_matches_statistics_quantiles():
    xs = [10.0, 11.0, 9.0, 10.5, 10.2, 9.8]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / statistics.median(xs))


def _record():
    rec = RunRecord(cell="x", window_s=10.0, spans=Spans(False))
    # 1,000 reads at 1..1000 ms, 10 writes at 5 s, 2 reads never done
    rec.kinds = ["get_edges"] * 1000 + ["create_edge"] * 10 + ["get_node"] * 2
    rec.latency_s = [i / 1000 for i in range(1, 1001)] + [5.0] * 10 \
        + [None, None]
    rec.done_in_window = 995
    return rec


def test_read_p95_is_over_every_read_and_skips_writes():
    read = spec.reader("read_p95_ms")
    assert read(_record()) == pytest.approx(950.0)


def test_ops_per_s_is_completions_over_the_whole_window():
    assert spec.reader("ops_per_s")(_record()) == pytest.approx(99.5)


def test_analytics_ms_is_span_over_requests():
    rec = RunRecord(cell="y", window_s=30.0, spans=Spans(False))
    rec.kinds = ["analytics"] * 40
    rec.latency_s = [0.8] * 40
    rec.span_s = 31.2
    assert spec.reader("analytics_ms")(rec) == pytest.approx(780.0)


def test_counter_readers():
    rec = _record()
    rec.counters0 = {"messages_sent": 100, "plan_cold_builds": 1,
                     "plan_delta_refreshes": 2}
    rec.counters1 = {"messages_sent": 10050, "plan_cold_builds": 3,
                     "plan_delta_refreshes": 10}
    rec.sim0, rec.sim1 = 1.0, 1.5
    assert spec.reader("msgs_per_op.tao")(rec) == pytest.approx(9950 / 995)
    assert spec.reader("plan_builds_per_kop.tao")(rec) == \
        pytest.approx(1e3 * 10 / 995)
    assert spec.reader("sim_rate.tao")(rec) == pytest.approx(0.05)


def test_readers_return_nothing_without_data():
    rec = RunRecord(cell="z", window_s=1.0, spans=Spans(False))
    for name in ("read_p95_ms", "ops_per_s", "analytics_ms",
                 "snapshot_ms.analytics", "graph_kernels_ms.analytics",
                 "plane_rows_synced.analytics", "mv_visibility_roofline",
                 "device_idle.tao", "device_idle.analytics"):
        assert spec.reader(name)(rec) is None, name
