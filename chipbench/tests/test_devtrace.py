import numpy as np
import pytest

from harness import devtrace, spec

SHAPE = {"replica": 0, "layout": ["clerk", "orderdate", "custkey"],
         "keys": [8, 7503872], "keys_itemsize": 4,
         "values": [8, 7503872], "values_itemsize": 4}

FUSED = ("%_fused_call.1 = (f32[32,128]{1,0}, s32[32,128]{1,0}) custom-call("
         "s32[8,7503872]{1,0} %keys.1, f32[8,7503872]{1,0} %values.1), "
         "custom_call_target=\"tpu_custom_call\"")
SELECT = "%_select_call.1 = (s32[8,128]{1,0}, s32[8,128]{1,0}) custom-call(...)"
COPY = "%copy.6 = s32[32,3]{1,0} copy(s32[32,3]{0,1} %res_lo.1)"


def synthetic():
    # window 0..10 ms; chip 0 busy 1-3 ms (fused, 2 ms), 2.5-4 ms (copy
    # overlapping it), 6-7 ms (select); one op straddles the window end
    ms = 1e6
    ops = [[(FUSED, 1 * ms, 3 * ms), (COPY, 2.5 * ms, 4 * ms),
            (SELECT, 6 * ms, 7 * ms), (COPY, 9.5 * ms, 12 * ms)]]
    labels = [("chipbench.window", 0.0, 10 * ms), ("client.serve", 0.6 * ms, 4.5 * ms),
              ("client.idle", 4.5 * ms, 6 * ms), ("client.serve", 6 * ms, 9.6 * ms)]
    return devtrace.DeviceTrace((0.0, 10 * ms), ops, labels)


def test_busy_is_union_clipped_to_window():
    t = synthetic()
    # 1-4 ms merged, 6-7 ms, 9.5-10 ms clipped
    assert t.busy_s == pytest.approx(4.5e-3)
    assert t.window_s == pytest.approx(10e-3)


def test_kernel_time_counts_only_matching_ops_inside_the_window():
    t = synthetic()
    events = t.op_events(lambda name: name.startswith("%_fused_call"))
    assert [(n[:12], secs) for n, secs in events] == [("%_fused_call", pytest.approx(2e-3))]
    # an op that straddles the window's end is not a whole launch
    assert t.op_events(lambda name: name.startswith("%copy")) == [(COPY, pytest.approx(1.5e-3))]


def test_idle_gaps_named_by_innermost_host_annotation():
    gaps = synthetic().idle_gaps(10)
    assert gaps[0] == ["client.serve", pytest.approx(2.5e-3)]  # 7-9.5 ms
    assert gaps[1][0] == "client.idle" and gaps[1][1] == pytest.approx(2e-3)  # 4-6
    assert gaps[2][0] == "chipbench.window" and gaps[2][1] == pytest.approx(1e-3)  # 0-1


def test_top_ops_group_by_hlo_name():
    top = dict((n, s) for n, s in synthetic().top_ops())
    assert top["_fused_call"] == pytest.approx(2e-3)
    assert top["copy"] == pytest.approx(1.5e-3 + 0.5e-3)


def test_fused_scan_bytes_from_shapes():
    per_query = 4 * (4 * 8 + 2 + 1) + 2 * 4 * 128
    assert devtrace.fused_scan_bytes(SHAPE, 32) == 2 * 8 * 7503872 * 4 + 32 * per_query
    # one launch streams about 480 MB of resident lanes
    assert 480e6 < devtrace.fused_scan_bytes(SHAPE, 8) < 481e6


class _Run:
    def __init__(self, trace, shapes):
        self.trace, self.shapes = trace, shapes
        self.peaks = {"hbm_bytes_per_s": 819e9}


def test_roofline_reader_from_synthetic_trace():
    read = spec.load_metric_reader("scan_agg_locate_roofline")
    got = read(_Run(synthetic(), [SHAPE]))
    want = 100 * devtrace.fused_scan_bytes(SHAPE, 32) / 819e9 / 2e-3
    assert got == pytest.approx(want)
    assert 0 < got <= 100


def test_roofline_reader_finds_nothing_without_a_launch():
    t = synthetic()
    t.ops[0] = [op for op in t.ops[0] if not op[0].startswith("%_fused")]
    assert spec.load_metric_reader("scan_agg_locate_roofline")(_Run(t, [SHAPE])) is None


def test_idle_reader():
    read = spec.load_metric_reader("device.idle_pct.r80")
    assert read(_Run(synthetic(), [SHAPE])) == pytest.approx(55.0)
    assert read(_Run(None, [SHAPE])) is None


def test_load_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=devtrace.profiler_options())
    with jax.profiler.TraceAnnotation(devtrace.WINDOW):
        with jax.profiler.TraceAnnotation("client.serve"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = devtrace.load(str(tmp_path), "TPU")
    assert t.window_s > 0
    assert t.ops == []  # no TPU plane on this host
    assert any(name == "client.serve" for name, _, _ in t.labels)
    assert np.all(np.array([e - s for _, s, e in t.labels]) >= 0)
