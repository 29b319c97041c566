"""The DBLP configuration (``dblp_2023_fig12``) and its session cell on the
CPU at a small shrink: both execution paths against the plain reference,
the JS-MV plan Algorithm 2 picks, the session operation's steps, and the
readers of the cell's three metrics."""
import numpy as np
import pytest

from bench import data, drive, harness, reference, spec
from repro.api import ExtractionEngine
from repro.core.pipeline import PipelineCompiler

CELL = "dblp_2023_fig12.session"
SHRINK = 50


@pytest.fixture(scope="module")
def cell():
    return spec.resolve(spec.load_benchmark(), CELL)


@pytest.fixture(scope="module")
def dblp(cell):
    config = cell["config"]
    tables = data.make_tables(config, 2**31 + 77, SHRINK)
    return (tables, harness._database(tables),
            harness._graph_model(config["graph"]), config)


def test_tables_keep_the_configured_shape(dblp):
    tables, _, _, config = dblp
    wrote = tables["wrote"]
    per_paper = np.bincount(wrote["p_sk"])[1:]
    assert per_paper.min() >= 1 and per_paper.max() <= 5
    assert abs(per_paper.mean() - 3.0) < 0.1
    # a paper's authors are distinct
    pairs = reference.pair_keys(wrote["p_sk"], wrote["a_sk"])
    assert len(np.unique(pairs)) == len(pairs)
    per_volume = np.bincount(tables["edits"]["v_sk"])[1:-1]   # last is cut
    assert per_volume.min() >= 2 and per_volume.max() <= 4
    assert set(tables["paper"]["v_sk"]) <= set(tables["venue"]["v_id"])
    assert list(tables) == ["author", "venue", "paper", "editor", "wrote",
                            "edits"]


@pytest.mark.parametrize("compiled", [False, True], ids=["eager", "compiled"])
def test_both_paths_match_the_reference_bag_for_bag(dblp, compiled):
    tables, db, model, config = dblp
    got = ExtractionEngine(db, compiled=compiled).extract(model)
    bags = reference.edge_bags(tables, config["graph"])
    have = drive.host_edges(got.graph)
    assert set(have) == set(bags) == {"Co-auth", "Auth-Edit"}
    for label, (s, d) in bags.items():
        assert len(s) > 0
        assert reference.bag_mismatch(have[label],
                                      reference.pair_keys(s, d)) == 0


def test_algorithm_2_picks_one_view_probed_three_times(dblp):
    _, db, model, _ = dblp
    res = ExtractionEngine(db).extract(model)
    plan = res.plan
    assert len(plan.views) == 1 and not plan.reused
    view = plan.views[0].name
    assert res.provenance.views_built == (view,)
    uses = [r for u in plan.units for r in u.single.relations
            if r.table == view]
    assert len(uses) == 3                  # twice in Co-auth, once Auth-Edit
    assert all(u.is_single for u in plan.units)


def test_each_session_step_builds_the_view_and_the_second_compiles_nothing(
        dblp, cell):
    _, db, model, config = dblp
    run = harness.Run(config=config, traffic=cell["traffic"], tables=None,
                      db=db, model=model, rng=np.random.default_rng(3))
    op = spec.operation("session").Operation(run, cell["traffic"])
    op.compiler = PipelineCompiler()
    held = []                  # what the operation holds as a session opens
    session = op._session
    op._session = lambda: (held.append((op.engine, op.last)), session())[1]
    compiles = harness._compile_counter()
    first = op.step()
    compiled, backend = op.compiler.stats["compiled"], compiles[0]
    second = op.step()
    assert first["ok"] and second["ok"]
    assert op.compiler.stats["compiled"] == compiled
    assert compiles[0] == backend
    assert op.counters() == {"retries": 0}
    assert "view_" not in " ".join(db.tables)    # the loaded db is untouched
    # the first session's engine and graph are gone before the second opens
    assert held == [(None, None), (None, None)]
    assert op.last[0] == 1
    assert list(op.kept) == [i for i in (0, 1) if i == op.keep_index]


def test_a_step_that_reuses_a_plan_or_view_fails(dblp, cell, monkeypatch):
    _, db, model, config = dblp
    run = harness.Run(config=config, traffic=cell["traffic"], tables=None,
                      db=db, model=model, rng=np.random.default_rng(3))
    module = spec.operation("session")
    op = module.Operation(run, cell["traffic"])
    op.compiler = PipelineCompiler()
    engine = ExtractionEngine(db, compiler=op.compiler)
    engine.extract(model)
    monkeypatch.setattr(op, "_session", lambda: engine.extract(model))
    assert not op.step()["ok"]             # plan and view came from the cache


def test_a_session_whose_plan_needs_no_view_is_ok():
    config = spec.resolve(spec.load_benchmark(),
                          "tpcds_sf1_fraud.extract")["config"]
    tables = data.make_tables(config, 2**31 + 5, 2000)
    run = harness.Run(config=config, traffic={}, tables=tables,
                      db=harness._database(tables),
                      model=harness._graph_model(config["graph"]),
                      rng=np.random.default_rng(5))
    op = spec.operation("session").Operation(run, {})
    op.compiler = PipelineCompiler()
    step = op.step()
    assert step["ok"] and not op.engine.extract(run.model).plan.views


def _run(spans=(), ops=2):
    run = harness.Run(config={}, traffic={}, tables={}, db=None, model=None,
                      rng=None, ops=[{}] * ops, trace=None)
    run.obs_spans = list(spans)
    return run


@pytest.mark.parametrize("metric,span", [
    ("view_ms.session", "view.build"),
    ("plan_search_ms.session", "plan.search"),
])
def test_span_readers(metric, span):
    reader = spec.metric_reader(metric)
    assert reader.read(_run()) is None                 # no such span
    other = {"name": "plan", "dur_s": 9.0}
    assert reader.read(_run([other])) is None
    spans = [{"name": span, "dur_s": 0.25}, {"name": span, "dur_s": 0.75},
             other]
    assert reader.read(_run(spans, ops=2)) == pytest.approx(500.0)


def test_estimate_slots_used_reader(monkeypatch):
    from repro import obs
    from repro.obs.metrics import MetricsRegistry

    reader = spec.metric_reader("estimate_slots_used.session")
    reg = MetricsRegistry()
    monkeypatch.setattr(obs, "REGISTRY", reg)
    assert reader.read(_run()) is None                 # no such counter
    reg.counter(reader.METRIC, rows="used")
    reg.counter(reader.METRIC, rows="allotted")
    assert reader.read(_run()) is None                 # nothing allotted
    reg.counter(reader.METRIC, rows="used").inc(742023)
    reg.counter(reader.METRIC, rows="allotted").inc(2097152)
    run = _run()
    assert reader.read(run) == pytest.approx(100 * 742023 / 2097152)
    assert run.notes == ["estimate-sized join-step slots: 742023 of 2097152 "
                         "allotted hold a row"]
