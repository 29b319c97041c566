"""The reduction from a profiler trace to device numbers, on made-up
events whose answers are known."""
import pytest

from bench import trace
from bench.trace import Event, Trace


def _trace():
    # window 0..100 ns; device busy 10-30, 20-40 (overlap), 60-70, and
    # 95-120 (clipped to 95-100); host spans label the gaps
    ops = [Event("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %bloom_probe.2)",
                 10, 30),
           Event("%bloom_probe.2 = s32[8]{0} custom-call(f32[2,128]{1,0} "
                 "%a, s32[8]{0} %b)", 20, 40),
           Event("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %bloom_probe.2)",
                 60, 70),
           Event("%sort.3 = s32[8]{0} sort(s32[8]{0} %c)", 95, 120)]
    host = [Event("bench.window", 0, 100), Event("bench.extract", 0, 100),
            Event("plan", 40, 55), Event("vertices", 70, 95)]
    return Trace(devices={"/device:TPU:0": ops}, host=host,
                 window=(0.0, 100.0))


def test_busy_union_and_idle_share():
    t = _trace()
    assert trace.union([(10, 30), (20, 40), (60, 70)]) == [(10, 40), (60, 70)]
    assert trace.busy_s(t) == pytest.approx(45e-9)
    assert trace.idle_share(t) == pytest.approx(0.55)
    assert t.window_s == pytest.approx(100e-9)


def test_busy_averages_over_devices():
    t = _trace()
    t.devices["/device:TPU:1"] = [Event("%fusion.1 = s32[8]{0} x()", 0, 100)]
    assert trace.busy_s(t) == pytest.approx((45e-9 + 100e-9) / 2)


def test_no_device_plane_reads_nothing():
    t = Trace(devices={}, host=[], window=(0.0, 10.0))
    assert trace.busy_s(t) is None and trace.idle_share(t) is None
    assert trace.idle_gaps(t) == []


def test_kernel_time_by_its_own_name_not_an_operand():
    t = _trace()
    evs = trace.kernel_events(t, ("bloom_probe",))
    assert [trace.op_name(e.name) for e in evs] == ["bloom_probe.2"]
    assert sum(e.dur_ns for e in evs) == 20
    assert trace.kernel_events(t, ("fusion",))[0].start_ns == 10


def test_top_ops_ranked_and_clipped():
    top = trace.top_ops(_trace())
    assert top[0][0] == "fusion.1"
    assert top[0][1] == pytest.approx(30e-9)
    assert dict(top)["sort.3"] == pytest.approx(5e-9)


def test_gaps_labelled_by_innermost_host_span():
    gaps = trace.idle_gaps(_trace())
    # gaps: 0-10 (extract), 40-60 (plan at its midpoint 50), 70-95
    # (vertices)
    assert [g[0] for g in gaps] == ["vertices", "plan", "bench.extract"]
    assert [g[1] for g in gaps] == pytest.approx([25e-9, 20e-9, 10e-9])


def test_load_reads_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.op"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    spans = [{"name": "plan", "start_s": 0.0, "dur_s": 1e-6}]
    t = trace.load(str(tmp_path), spans, window_start_perf=0.0)
    assert t.window_s > 0
    assert {"bench.window", "bench.op", "plan"} <= {h.name for h in t.host}
    assert t.devices == {}          # the CPU has no TPU plane
