"""The reduction from a profiler trace to busy time, idle gaps by host
span and per-operation device time, on a small trace committed as a
fixture."""

import os

import pytest

from bench.harness import spec, trace

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    with open(os.path.join(FIXTURES, "small_trace.pbtxt")) as f:
        return trace.reduce_profile(ProfileData.from_text_proto(f.read()))


def test_window_and_busy(reduced):
    assert reduced["window_s"] == pytest.approx(10e-6)
    assert reduced["devices"] == 2
    # TPU:0 2.5-3.0 and the union 7.0-8.0; TPU:1 6-7; ops outside the
    # window are clipped away
    assert reduced["busy_s"] == pytest.approx((1.5e-6 + 1.0e-6) / 2)


def test_device_ops_per_name(reduced):
    ops = dict(reduced["device_ops"])
    assert ops["fusion.1"] == pytest.approx((0.5e-6 + 1.0e-6) / 2)
    assert ops["before.3"] == pytest.approx((0.5e-6 + 0.75e-6) / 2)
    assert "jit_step" not in ops          # modules are not operations
    assert reduced["device_ops"][0][0] == "fusion.1"
    assert len(reduced["events"]) == 4     # two lie outside the window


def test_idle_gaps_by_innermost_host_span(reduced):
    gaps = dict(reduced["idle_gaps"])
    # TPU:0 idle 1-2.5, 3-7, 8-11; TPU:1 idle 1-6, 7-11
    assert gaps["sim_run"] == pytest.approx((0.5e-6 + 1.0e-6) / 2)
    assert gaps["churn"] == pytest.approx(1.0e-6)
    assert gaps["kernels"] == pytest.approx(3.0e-6)
    assert gaps["none"] == pytest.approx(4.0e-6)
    busy_and_idle = reduced["busy_s"] + sum(gaps.values())
    assert busy_and_idle == pytest.approx(reduced["window_s"])


def test_no_window_span_is_an_error():
    from jax.profiler import ProfileData

    with open(os.path.join(FIXTURES, "small_trace.pbtxt")) as f:
        txt = f.read().replace('"bench.window"', '"bench.other"')
    with pytest.raises(RuntimeError):
        trace.reduce_profile(ProfileData.from_text_proto(txt))


def test_capture_and_reduce_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    x = jnp.ones(8)
    f(x).block_until_ready()
    with trace.capture(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            with jax.profiler.TraceAnnotation("bench.kernels"):
                f(x).block_until_ready()
    out = trace.reduce(trace.find_xplane(str(tmp_path)))
    assert out["window_s"] > 0 and out["busy_s"] == 0.0
    assert dict(out["idle_gaps"]).get("kernels", 0) > 0


def test_recorded_v5e_trace_and_kernel_bytes():
    """A trace recorded on one v5e: the kernel's launches are found by
    name, their bytes come from the shapes, and its roofline share is a
    share."""
    from jax.profiler import ProfileData

    from bench.harness import kernel_cost
    from bench.harness.cell import RunRecord

    with open(os.path.join(FIXTURES, "v5e_probe.pbtxt")) as f:
        out = trace.reduce_profile(ProfileData.from_text_proto(f.read()))
    launches = [(kernel_cost.mv_visibility_bytes(n), s)
                for n, s, _ in out["events"]
                if kernel_cost.mv_visibility_bytes(n) is not None]
    # two rounds of: before_mask at 1,024 and 4,096 rows, the plane's
    # sharded launch over 8 x 4 x 2,048 rows; C = 5 stamp components
    assert sorted(b for b, _ in launches) == sorted(
        [4 * (n + 5 + 5 * n) for n in (1024, 4096, 65536)] * 2)
    assert out["device_ops"][0][0] == "%before_pallas.1"
    rec = RunRecord(cell="x", trace=out, device_kind="TPU v5 lite")
    share = spec.reader("mv_visibility_roofline")(rec)
    assert 0 < share <= 100
    idle = spec.reader("device_idle.analytics")(rec)
    assert 99 < idle < 100


def test_kernel_bytes_ignore_other_operations():
    from bench.harness import kernel_cost

    assert kernel_cost.mv_visibility_bytes(
        "%fusion.3 = f32[4096]{0} fusion(s32[60000]{0} %a)") is None
    assert kernel_cost.hlo_bytes(
        "%fusion.3 = f32[4096]{0} fusion(s32[60000]{0} %a, bf16[2,3]{1,0} "
        "%b)") == 4 * 4096 + 4 * 60000 + 2 * 6
