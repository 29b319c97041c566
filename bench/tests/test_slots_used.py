"""The reader of ``slots_used.extract``, on made-up counters whose answer is
known."""

import pytest

from bench import spec


def _run():
    from bench.harness import Run

    return Run(config={}, traffic={}, tables={}, db=None, model=None,
               rng=None, ops=[{}], trace=None)


def test_slots_used_from_the_registry(monkeypatch):
    from repro import obs
    from repro.obs.metrics import MetricsRegistry

    reader = spec.metric_reader("slots_used.extract")
    reg = MetricsRegistry()
    monkeypatch.setattr(obs, "REGISTRY", reg)
    assert reader.read(_run()) is None            # no such counter
    reg.counter(reader.METRIC, rows="used")
    reg.counter(reader.METRIC, rows="allotted")
    assert reader.read(_run()) is None            # nothing allotted
    reg.counter(reader.METRIC, rows="used").inc(2880404)
    reg.counter(reader.METRIC, rows="allotted").inc(8388608)
    run = _run()
    assert reader.read(run) == pytest.approx(100 * 2880404 / 8388608)
    assert run.notes == ["join-step slots: 2880404 of 8388608 allotted "
                         "hold a row"]
