"""The reader of ``range_probe.extract``, on made-up counters whose answer is
known."""

import pytest

from bench import spec


def _run():
    from bench.harness import Run

    return Run(config={}, traffic={}, tables={}, db=None, model=None,
               rng=None, ops=[{}], trace=None)


def test_range_probe_from_the_registry(monkeypatch):
    from repro import obs
    from repro.obs.metrics import MetricsRegistry

    reader = spec.metric_reader("range_probe.extract")
    reg = MetricsRegistry()
    monkeypatch.setattr(obs, "REGISTRY", reg)
    assert reader.read(_run()) is None            # no such counter
    reg.counter(reader.METRIC, method="range")
    reg.counter(reader.METRIC, method="search")
    assert reader.read(_run()) is None            # nothing probed
    reg.counter(reader.METRIC, method="range").inc(5760000)
    reg.counter(reader.METRIC, method="search").inc(1920000)
    run = _run()
    assert reader.read(run) == pytest.approx(75.0)
    assert run.notes == ["join probes: 5760000 of 7680000 probe rows "
                         "through a range table"]
