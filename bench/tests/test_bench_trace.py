"""The reduction from trace events to device busy time, top ops and idle
gaps, and the kernel-time reader."""

import pytest

from bench import devtrace, harness

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, name, start, dur, line=None):
    line = line or (devtrace.OPS_LINE if plane == DEV else "python")
    return (plane, line, name, start, dur)


def test_merge_unions_overlapping_intervals():
    got = devtrace.merge([(5, 7), (0, 2), (1, 3), (6, 9), (10, 11)])
    assert got.tolist() == [[0, 3], [5, 9], [10, 11]]
    assert devtrace.merge([]).shape == (0, 2)


def test_reduce_busy_top_ops_and_gap_attribution():
    events = [
        ev(HOST, "traced_window", 0, 1000),
        ev(HOST, "request", 100, 500),
        ev(HOST, "plan", 100, 200),          # host plans: device idle
        ev(HOST, "request", 700, 250),
        ev(DEV, "scan", 300, 200),
        ev(DEV, "fusion", 450, 100),         # overlaps the scan
        ev(DEV, "plan_fuse.1", 750, 100),
        ev(DEV, "scan", 990, 50),            # runs past the window's end
        ev(DEV, "other", 2000, 5, line="XLA Modules"),
    ]
    events = [e for e in events if e[1] in (devtrace.OPS_LINE, "python")]
    s = devtrace.reduce(events)
    assert s["window_s"] == pytest.approx(1000e-9)
    assert s["busy_s"] == pytest.approx((250 + 100 + 10) * 1e-9)
    assert s["requests"] == 2
    assert s["device_ops"][0] == ["scan", pytest.approx(210e-9)]
    assert s["op_seconds"]["plan_fuse.1"] == pytest.approx(100e-9)
    gaps = dict(s["idle_gaps"])
    assert gaps["plan"] == pytest.approx(200e-9)       # 100..300
    assert gaps["request"] == pytest.approx(200e-9)    # 550..600, 700..750,
    #                                                    850..950
    assert gaps["between requests"] == pytest.approx(
        (100 + 100 + 40) * 1e-9)                       # 0..100, 600..700, 950..990
    total_idle = sum(gaps.values())
    assert total_idle + s["busy_s"] == pytest.approx(s["window_s"])


def test_reduce_refuses_a_window_with_no_device_op():
    with pytest.raises(ValueError, match="no device operation"):
        devtrace.reduce([ev(HOST, "traced_window", 0, 10)])


def test_read_a_recorded_profiler_trace(tmp_path):
    """The harness's spans, recorded by the real profiler and read back from
    its ``.xplane.pb``: the host plane and the spans' clock are what the
    reduction expects.  The CPU has no device plane, so one device op is
    put on that clock by hand, inside the first request."""
    import jax
    import jax.numpy as jnp

    devtrace.start(str(tmp_path))
    with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
        for _ in range(2):
            with jax.profiler.TraceAnnotation(devtrace.REQUEST_SPAN):
                with jax.profiler.TraceAnnotation("plan"):
                    jnp.arange(1000).block_until_ready()
    jax.profiler.stop_trace()
    events = devtrace.read_xplane(str(tmp_path), ["plan"])
    names = sorted(e[2] for e in events)
    assert names == ["plan", "plan", "request", "request", "traced_window"]
    assert {e[0] for e in events} == {HOST}
    win = next(e for e in events if e[2] == devtrace.WINDOW_SPAN)
    req = min((e for e in events if e[2] == devtrace.REQUEST_SPAN),
              key=lambda e: e[3])
    assert win[3] <= req[3] and req[3] + req[4] <= win[3] + win[4]
    op = (req[3] + req[3] + req[4]) / 2
    s = devtrace.reduce(events + [ev(DEV, "fusion", op, 1000)])
    assert s["requests"] == 2
    assert s["busy_s"] == pytest.approx(1e-6)
    assert s["window_s"] == pytest.approx(win[4] * 1e-9)
    assert sum(dict(s["idle_gaps"]).values()) + s["busy_s"] == \
        pytest.approx(s["window_s"])


def test_window_ends_before_the_profiler_dropped_its_buffers():
    """Past a drop the trace holds no device ops: the window ends with the
    last request that ended before it, and only its ops and gaps count."""
    events = [
        ev(HOST, "traced_window", 0, 1000),
        ev(HOST, "request", 0, 300),
        ev(HOST, "request", 300, 300),
        ev(HOST, "request", 600, 300),
        ev(DEV, "scan", 100, 100),
        ev(DEV, "scan", 400, 100),
        ev(DEV, devtrace.DROPPED, 650, 5000, line="XLA TraceMe"),
    ]
    s = devtrace.reduce(events)
    assert s["dropped"] is True
    assert s["window_s"] == pytest.approx(600e-9)
    assert s["requests"] == 2
    assert s["busy_s"] == pytest.approx(200e-9)
    assert devtrace.DROPPED not in s["op_seconds"]
    assert sum(dict(s["idle_gaps"]).values()) == pytest.approx(400e-9)
    assert devtrace.reduce(events[:-1])["dropped"] is False
    with pytest.raises(ValueError, match="dropped its buffers"):
        devtrace.reduce(events[:-1] + [ev(DEV, devtrace.DROPPED, 250, 9,
                                          line="XLA TraceMe")])


def test_kernel_time_reader():
    read = harness.metric_reader("planfuse_ms_per_query")
    # the trace names an op by its HLO text; ops of two programs add up,
    # an op that merely mentions the kernel is not the kernel
    ops = {"%fusion.8 = u32[64]{0} fusion(s32[64]{0} %p.1)": 1.0,
           "%plan_fuse.1 = (u32[8,64]{1,0}) custom-call(u32[8,64]{1,0} "
           "%a), custom_call_target=\"tpu_custom_call\"": 3e-3,
           "%plan_fuse = u32[64]{0} custom-call(u32[64]{0} %b)": 1e-3,
           "%pallas_call.1 = u32[64]{0} get-tuple-element(%plan_fuse.1)": 1.0,
           "x_plan_fuse": 1.0}
    run = {"trace": {"op_seconds": ops, "requests": 2}}
    assert read(run) == pytest.approx(2.0)
    assert read(dict(run, trace=None)) is None
    assert read({"trace": {"op_seconds": ops, "requests": 0}}) is None
    assert read({"trace": {"op_seconds": {"other": 1.0},
                           "requests": 2}}) is None
