"""The readers of the tracing metrics, on made-up events and counters whose
answers are known."""

import pytest

from bench import spec
from bench.trace import Event, Trace


def _run(trace=None, spans=(), ops=1):
    from bench.harness import Run

    run = Run(config={}, traffic={}, tables={}, db=None, model=None,
              rng=None, ops=[{}] * ops, trace=trace)
    run.obs_spans = list(spans)
    return run


def _khop_trace(host_extra=()):
    # window 0..100 ns; device busy 0-20, 30-50, 80-90
    ops = [Event("%while.2 = s32[8]{0} while(...)", 0, 20),
           Event("%fusion.4 = s32[8]{0} fusion(...)", 30, 50),
           Event("%frontier_expand.7 = ...", 80, 90)]
    host = [Event("bench.window", 0, 100), Event("bench.analyze", 0, 100),
            Event("engine.analyze", 0, 95),
            Event("pipeline.wait", 15, 28),      # idle 20-28: unexplained
            Event("csr", 50, 80),
            Event("graph.fetch", 50, 60),        # idle 50-60: host work
            Event("graph.digest", 60, 75),       # idle 60-75: host work
            *host_extra]
    # idle 75-80 under csr (host work); idle 90-95 under engine.analyze
    # (host work); idle 95-100 under no program span: unexplained
    return Trace(devices={"/device:TPU:0": ops}, host=host,
                 window=(0.0, 100.0))


def test_idle_unexplained_counts_wait_spans_and_no_span():
    reader = spec.metric_reader("idle_unexplained.analyze")
    assert reader.read(_run(_khop_trace())) == pytest.approx(13.0)
    # a second device idle all window under the same host spans
    t = _khop_trace()
    t.devices["/device:TPU:1"] = []
    # device 1: every .wait or span-less instant is idle: 15-28, 95-100
    assert reader.read(_run(t)) == pytest.approx((13.0 + 18.0) / 2)


def test_idle_unexplained_reads_nothing_without_the_wait_rule(monkeypatch):
    from repro.obs import trace as spans

    reader = spec.metric_reader("idle_unexplained.analyze")
    t = _khop_trace()
    assert reader.read(_run(Trace({}, t.host, (0.0, 100.0)))) is None
    assert reader.read(_run(None)) is None
    monkeypatch.delattr(spans, "WAIT_SUFFIX")       # a program without it
    assert reader.read(_run(t)) is None


def test_idle_unexplained_innermost_span_decides():
    reader = spec.metric_reader("idle_unexplained.analyze")
    # a host copy inside a wait (e.g. an input transfer) is host work
    t = _khop_trace(host_extra=[Event("pipeline.input", 20, 24)])
    assert reader.read(_run(t)) == pytest.approx(9.0)


def test_digest_ms_is_the_span_per_operation():
    reader = spec.metric_reader("digest_ms.analyze")
    spans = [{"name": "graph.digest", "dur_s": 0.9},
             {"name": "graph.digest", "dur_s": 1.1},
             {"name": "graph.fetch", "dur_s": 5.0}]
    assert reader.read(_run(spans=spans, ops=2)) == pytest.approx(1000.0)
    assert reader.read(_run(spans=spans[2:], ops=2)) is None


def test_bloom_pass_from_the_registry(monkeypatch):
    from repro import obs
    from repro.obs.metrics import MetricsRegistry

    reader = spec.metric_reader("bloom_pass.extract")
    reg = MetricsRegistry()
    monkeypatch.setattr(obs, "REGISTRY", reg)
    assert reader.read(_run()) is None            # no such counter
    reg.counter(reader.METRIC, outcome="probed")
    reg.counter(reader.METRIC, outcome="passed")
    assert reader.read(_run()) == 100.0           # nothing probed
    reg.counter(reader.METRIC, outcome="probed").inc(400)
    reg.counter(reader.METRIC, outcome="passed").inc(390)
    run = _run()
    assert reader.read(run) == pytest.approx(97.5)
    assert run.notes == ["bloom prefilter: 390 of 400 probe rows passed"]


def test_join_ms_unions_ops_under_join_and_outer_scopes():
    reader = spec.metric_reader("join_ms.extract")
    ops = [Event("%while.24 = (s32[]) while(...)", 10, 50),   # outer
           Event("%fusion.106 = s32[8] fusion(...)", 20, 30),  # in the while
           Event("%fusion.3 = s32[8] fusion(...)", 50, 60),    # join
           Event("%fusion.37 = s32[8] fusion(...)", 60, 70),   # dedup
           Event("%fusion.3 = s32[8] fusion(...)", 80, 90),    # other module
           Event("%fusion.3 = s32[8] fusion(...)", 95, 120)]   # clipped
    modules = {"/device:TPU:0": [(0, 75, "jit_unit_Sell_Buy"),
                                 (78, 92, "jit_khop"),
                                 (93, 130, "jit_unit_Sell_Buy")]}
    scopes = {"jit_unit_Sell_Buy": {"while.24": "outer:Buy__u0",
                                    "fusion.106": "outer:Buy__u0",
                                    "fusion.3": "join:p0",
                                    "fusion.37": "dedup:Buy"}}
    t = Trace(devices={"/device:TPU:0": ops}, host=[], window=(0.0, 100.0))
    notes = []
    # (10-50) + (50-60) + (95-100) = 55 ns over 2 operations
    got = reader.join_ms(t, modules, scopes, operations=2, notes=notes)
    assert got == pytest.approx(27.5e-6)
    assert notes and notes[0].startswith("device time by step scope: "
                                         "outer:Buy__u0")
    assert reader.join_ms(t, modules, {}, operations=2) is None
    assert reader.join_ms(t, {}, scopes, operations=2) is None
    assert reader.read(_run(None)) is None
