"""The check can fail.

* Faults planted in the timed path, under a run of the harness that skips
  only its look for a chip: each must turn ``correct`` false.  The faults
  each cell can have: an answer altered where it is produced; half of the
  batch left out (half of an extract's edges, half of a request's seeds).
  No cell keeps state that a step advances, and none exchanges anything
  between chips.
* The control (the reference with int16 vertex ids, the precision below
  the configuration's int32) put in the program's place, through the
  cell's own comparison, fails a number of every cell, at sizes whose ids
  exceed int16.
"""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, data, harness, spec
from repro.api import ExtractionEngine
from repro.graph import algorithms
from repro.relational import Table
from repro.serving import GraphService

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


def run(cell):
    return harness.run_cell(cell, 2**31 + 21, 0.3, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            shrink=2000)


def _break_edges(monkeypatch, how):
    real = ExtractionEngine.extract

    def broken(self, model, *a, **k):
        res = real(self, model, *a, **k)
        label = sorted(res.graph.edges)[-1]
        t = res.graph.edges[label]
        if how == "half":
            keep = jnp.arange(t.capacity) % 2 == 0
            t = Table(columns=t.columns, valid=t.valid & keep)
        else:
            first = int(np.argmax(np.asarray(t.valid)))
            t = t.with_columns(dst=t["dst"].at[first].add(1))
        res.graph.edges[label] = t
        res.graph._fp = None
        return res

    monkeypatch.setattr(ExtractionEngine, "extract", broken)


@pytest.mark.parametrize("how", ["half", "altered"])
def test_extract_fault_is_caught(monkeypatch, how):
    _break_edges(monkeypatch, how)
    result = run("tpcds_sf1_fraud.extract")
    assert not result["correct"]
    assert result["checks"]["edge_mismatch"]["value"] > 0


def test_khop_altered_answer_is_caught(monkeypatch):
    real = algorithms.ALGORITHMS["khop"]

    def broken(csr, *a, **k):
        return real(csr, *a, **k).at[0].add(1)

    monkeypatch.setitem(algorithms.ALGORITHMS, "khop", broken)
    result = run("tpcds_sf1_fraud.khop")
    assert not result["correct"]
    assert result["checks"]["khop_answers_wrong"]["value"] > 0


def test_khop_half_of_the_seeds_left_out_is_caught(monkeypatch):
    real = GraphService.analyze

    def half(self, name, seeds=None, **k):
        return real(self, name, seeds=seeds[: len(seeds) // 2], **k)

    monkeypatch.setattr(GraphService, "analyze", half)
    result = run("tpcds_sf1_fraud.khop")
    assert not result["correct"]
    assert result["checks"]["khop_answers_wrong"]["value"] == \
        result["attempted"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_a_number_of_each_cell(cell):
    checks = control.control_checks(cell, 2**31 + 5, shrink=2, count=1)
    assert any(v > lim for v, lim in checks.values()), checks


@pytest.mark.parametrize("cell", CELLS)
def test_control_at_the_configured_precision_passes(cell):
    """The control's path with the configuration's own int32 ids reads
    every number within its limit: what fails above is the precision."""
    cfg = spec.resolve(spec.load_benchmark(), cell)
    run_ = harness.Run(config=cfg["config"], traffic=cfg["traffic"],
                       tables=data.make_tables(cfg["config"], 9,
                                               shrink=2000),
                       db=None, model=None, rng=np.random.default_rng(9))
    operation = spec.operation(cfg["traffic"]["operation"])
    answers = operation.control_answers(run_, cfg["traffic"], np.int32, 2)
    checks = operation.compare(run_, cfg["traffic"], answers)
    assert all(v <= lim for v, lim in checks.values()), checks
