"""Compiled-pipeline correctness: bag parity with the eager executor.

The compiled path (one fused jitted executable per plan unit, capacities
pre-sized from the cost model, on-device overflow detection) must produce
*identical* edge tables — valid-row bag equality via ``table_digest`` — to
the eager two-phase count→expand path, for every workload and including
the overflow-retry branch (forced here with an artificially low capacity
clamp).
"""
import numpy as np
import pytest

from repro.api import ExtractionEngine
from repro.core.extract import plan_queries, run_plan
from repro.core.model import JoinQuery
from repro.core.pipeline import (
    ESTIMATE,
    PROBE_BOUND,
    PipelineCompiler,
    build_query_program,
    clear_executable_cache,
    probe_capacities,
)
from repro.data import (
    combined_model,
    dblp_model,
    fraud_model,
    imdb_model,
    make_dblp,
    make_imdb,
    make_tpcds,
    recommendation_model,
)
from repro.relational.ops import table_digest


def _digests(edges):
    return {label: table_digest(t) for label, t in edges.items()}


@pytest.fixture(scope="module")
def tpcds_db():
    return make_tpcds(sf=1, seed=0)


@pytest.fixture(scope="module")
def dblp_db():
    return make_dblp(scale=1, seed=1)


@pytest.fixture(scope="module")
def imdb_db():
    return make_imdb(scale=1, seed=2)


@pytest.mark.parametrize("model_fn,db_name", [
    (lambda: fraud_model("store"), "tpcds_db"),
    (lambda: recommendation_model("store"), "tpcds_db"),
    (combined_model, "tpcds_db"),
    (dblp_model, "dblp_db"),
    (imdb_model, "imdb_db"),
])
def test_compiled_plan_matches_eager(model_fn, db_name, request):
    db = request.getfixturevalue(db_name)
    model = model_fn()
    plan = plan_queries(db.snapshot(), model.queries(), "extgraph")
    eager = run_plan(db.snapshot(), plan)[0]
    compiled = run_plan(db.snapshot(), plan,
                        compiler=PipelineCompiler())[0]
    assert _digests(compiled) == _digests(eager)


def test_overflow_retry_matches_eager(tpcds_db):
    """An 8-row capacity clamp truncates every join; the on-device required
    counts must drive retries up to exact buckets with identical results."""
    model = fraud_model("store")
    plan = plan_queries(tpcds_db.snapshot(), model.queries(), "extgraph")
    eager = run_plan(tpcds_db.snapshot(), plan)[0]
    comp = PipelineCompiler(initial_capacity_clamp=8)
    compiled = run_plan(tpcds_db.snapshot(), plan, compiler=comp)[0]
    assert comp.stats["retries"] > 0
    assert _digests(compiled) == _digests(eager)
    # proven capacities are remembered: a replay skips the retry dance
    retries = comp.stats["retries"]
    again = run_plan(tpcds_db.snapshot(), plan, compiler=comp)[0]
    assert comp.stats["retries"] == retries
    assert _digests(again) == _digests(eager)


def test_overflow_retry_on_merged_unit(tpcds_db):
    """The JS-OJ (outer-join group) path also detects and heals overflow."""
    model = recommendation_model("store")
    plan = plan_queries(tpcds_db.snapshot(), model.queries(), "extgraph-oj")
    assert any(not u.is_single for u in plan.units), "expected a JS-OJ group"
    eager = run_plan(tpcds_db.snapshot(), plan)[0]
    comp = PipelineCompiler(initial_capacity_clamp=8)
    compiled = run_plan(tpcds_db.snapshot(), plan, compiler=comp)[0]
    assert comp.stats["retries"] > 0
    assert _digests(compiled) == _digests(eager)


def test_kernel_probe_and_bloom_parity(tpcds_db):
    """Forcing the Pallas sorted_probe + bloom prefilter (interpret mode on
    CPU) must not change any result bag."""
    model = fraud_model("store")
    plan = plan_queries(tpcds_db.snapshot(), model.queries(), "extgraph")
    eager = run_plan(tpcds_db.snapshot(), plan)[0]
    comp = PipelineCompiler(use_kernel=True, use_bloom=True)
    assert comp.use_kernel and comp.use_bloom and comp.probe_kernel
    compiled = run_plan(tpcds_db.snapshot(), plan, compiler=comp)[0]
    assert _digests(compiled) == _digests(eager)


def test_executable_cache_shared_across_engines(tpcds_db):
    """Warm executable cache + cold data: a second engine over a fresh
    database with the same schema replays compiled executables."""
    clear_executable_cache()
    model = fraud_model("store")
    comp = PipelineCompiler()
    e1 = ExtractionEngine(tpcds_db, compiler=comp)
    cold = e1.extract(model)
    misses = comp.stats["misses"]
    assert misses > 0 and comp.stats["compiled"] > 0

    db2 = make_tpcds(sf=1, seed=3)
    e2 = ExtractionEngine(db2, compiler=comp)
    second = e2.extract(model)
    assert comp.stats["hits"] > 0
    # same capacity buckets + schema -> zero new compiles
    assert comp.stats["misses"] == misses
    # and the result is the fresh database's graph, not the first one's
    oracle, _, _ = run_plan(
        db2.snapshot(),
        plan_queries(db2.snapshot(), model.queries(), "extgraph"))
    assert _digests(second.edges) == _digests(oracle)
    assert _digests(second.edges) != _digests(cold.edges)

    info = e2.cache_info()
    assert info["executable_hits"] > 0
    assert info["executables"] > 0


def test_engine_compiled_matches_eager_engine(tpcds_db):
    """End-to-end: compiled engine == eager engine == same provenance."""
    model = combined_model()
    compiled = ExtractionEngine(tpcds_db).extract(model)
    eager = ExtractionEngine(tpcds_db, compiled=False).extract(model)
    assert _digests(compiled.edges) == _digests(eager.edges)
    assert set(compiled.vertices) == set(eager.vertices)


def test_query_program_capacities_are_pow2(tpcds_db):
    """Estimate-sized steps are pow-2 buckets; a step into a unique key
    holds its probe side's capacity instead."""
    prog = build_query_program(
        tpcds_db, fraud_model("store").queries()[0], edges=True)
    assert prog.kind == "edges"
    assert len(prog.capacities) == 2          # two joins in a 3-table chain
    probes = probe_capacities(tpcds_db, prog)
    for cap, sizing, probe in zip(prog.capacities, prog.sizing, probes):
        if sizing == ESTIMATE:
            assert cap >= 8 and (cap & (cap - 1)) == 0, cap
        else:
            assert sizing == PROBE_BOUND and cap == probe, (cap, probe)


def test_vertices_ride_along_compiled(tpcds_db):
    res = ExtractionEngine(tpcds_db).extract(fraud_model("store"))
    assert set(res.vertices) == {"Customer", "Item", "Outlet"}
    cust = res.vertices["Customer"].to_numpy()
    assert len(cust["id"]) == int(tpcds_db.stats["customer"].rows)
    for label, t in res.edges.items():
        data = t.to_numpy()
        assert data["src"].dtype == np.int32
        assert (data["src"] >= 0).all() and (data["dst"] >= 0).all()


def test_failed_background_recompile_is_counted():
    from repro import obs
    from repro.core import pipeline

    before = obs.REGISTRY.value(pipeline.REOPT_FAILURES)
    pipeline._submit_reopt(lambda: 1 // 0)
    pipeline.drain_reoptimizations()
    assert obs.REGISTRY.value(pipeline.REOPT_FAILURES) == before + 1


def test_bloom_counts_reach_stats_and_registry(tpcds_db):
    """The pipeline sums each kept attempt's prefilter pairs into its stats
    and ``pipeline_bloom_rows_total``; with the prefilter off it counts
    nothing."""
    from repro import obs

    model = fraud_model("store")
    plan = plan_queries(tpcds_db.snapshot(), model.queries(), "extgraph")

    def registry():
        return [obs.REGISTRY.value("pipeline_bloom_rows_total",
                                   outcome=o) for o in ("probed", "passed")]

    off = PipelineCompiler(use_kernel=False, use_bloom=False)
    before = registry()
    run_plan(tpcds_db.snapshot(), plan, compiler=off)
    assert (off.stats["bloom_probed"], off.stats["bloom_passed"]) == (0, 0)
    assert registry() == before

    on = PipelineCompiler(use_kernel=False, use_bloom=True)
    run_plan(tpcds_db.snapshot(), plan, compiler=on)
    probed, passed = on.stats["bloom_probed"], on.stats["bloom_passed"]
    assert 0 < passed <= probed
    assert registry() == [before[0] + probed, before[1] + passed]


def test_merged_unit_hlo_names_its_unit_and_steps(tpcds_db):
    """A merged unit's executable is named after its members, and its ops
    carry the ``join:`` / ``outer:`` / ``dedup:`` scopes (a scan without a
    filter adds no op)."""
    import re

    from repro.core import pipeline

    model = recommendation_model("store")
    plan = plan_queries(tpcds_db.snapshot(), model.queries(), "extgraph-oj")
    merged = [u.group for u in plan.units if not u.is_single]
    assert merged, "expected a JS-OJ group"
    run_plan(tpcds_db.snapshot(), plan, compiler=PipelineCompiler())
    texts = {exe.as_text().split(",", 1)[0]: exe.as_text()
             for exe in pipeline._EXECUTABLE_CACHE.values()}
    label = re.sub(r"[^0-9A-Za-z_]", "_", "_".join(merged[0].member_names()))
    module = "HloModule jit_unit_" + label
    assert module in texts, sorted(texts)
    scopes = set(re.findall(r'op_name="jit\(unit_\w+\)/(\w+):',
                            texts[module]))
    assert {"join", "outer", "dedup"} <= scopes, scopes


# -- capacities of steps into a unique key: the probe side bounds them -------

def _bench_fraud(shrink):
    """The chip benchmark's fraud tables and model at 1/``shrink`` scale:
    dsdgen-shaped ``store_sales`` whose three dimensions are keyed by
    unique surrogate keys."""
    from bench import data as bench_data
    from bench import harness, spec

    config = spec.resolve(spec.load_benchmark(),
                          "tpcds_sf1_fraud.extract")["config"]
    db = harness._database(bench_data.make_tables(config, 7, shrink))
    return db, harness._graph_model(config["graph"])


def _programs_run(engine):
    return list(engine.compiler._programs.values())


def test_unique_keys_bound_every_fraud_step_by_its_probe_side():
    db, model = _bench_fraud(shrink=200)
    engine = ExtractionEngine(db, compiler=PipelineCompiler())
    got = engine.extract(model)
    merged = [p for p in _programs_run(engine) if p.kind == "merged"]
    assert len(merged) == 1, "expected the merged Sell+Buy unit"
    prog = merged[0]
    assert prog.sizing == (PROBE_BOUND,) * 3
    fact = db.tables["store_sales"].capacity
    assert probe_capacities(db, prog) == prog.capacities == (fact,) * 3
    assert engine.compiler.stats["retries"] == 0
    eager = ExtractionEngine(db, compiled=False).extract(model)
    assert _digests(got.edges) == _digests(eager.edges)


def test_steps_into_non_unique_keys_and_views_keep_the_estimate(dblp_db):
    from repro.core.cost import step_expansions
    from repro.core.model import join_schedule
    from repro.core.pipeline import CAPACITY_MARGIN, _bucket
    from repro.data.dblp import coauth_query

    query = coauth_query()
    prog = build_query_program(dblp_db, query, edges=True)
    rows = step_expansions(dblp_db, query, prog.orders[0])
    schedule = join_schedule(query, prog.orders[0])
    wrote = 0
    for (alias, _, _), cap, sizing, r in zip(schedule, prog.capacities,
                                             prog.sizing, rows):
        if query.relation(alias).table == "wrote":
            wrote += 1
            assert sizing == ESTIMATE
            assert cap == _bucket(r, CAPACITY_MARGIN, None)
    assert wrote

    # a JS-MV view carries estimated stats, whose ndv is capped at the row
    # estimate: here ``W.p_sk`` looks unique (capped at an estimated 5,400
    # rows) but is not; no step into it is bound
    from repro.core.executor import ensure_view
    from repro.core.model import ColumnRef, JoinCond, Predicate, Relation

    db = dblp_db.snapshot()
    view = JoinQuery(
        "v", (Relation("W", "wrote", (Predicate("rid", "<", 5400),
                                      Predicate("rid", "!=", 7))),),
        (), ColumnRef("W", "rid"), ColumnRef("W", "rid"))
    ensure_view(db, "v", view)
    st = db.stats["v"]
    assert st.estimated and st.distinct["W.p_sk"] >= st.rows
    data = db.tables["v"].to_numpy()
    assert len(np.unique(data["W.p_sk"])) < len(data["W.p_sk"])
    query = JoinQuery(
        "into_view", (Relation("P", "paper"), Relation("V", "v")),
        (JoinCond("P", "p_id", "V", "W.p_sk"),),
        ColumnRef("P", "p_id"), ColumnRef("V", "W.a_sk"))
    prog = build_query_program(db, query, edges=True)
    assert prog.orders == (("P", "V"),)                # the view is built
    assert prog.capacities[0] >= db.tables["paper"].capacity
    assert prog.sizing == (ESTIMATE,)
    assert prog.capacities == (_bucket(prog.est_rows[0], CAPACITY_MARGIN,
                                       None),)


def test_duplicate_key_behind_unique_stats_retries_to_the_exact_bag():
    """A row inserted with an existing item key keeps the stats' claim that
    the key is unique; the bound step overflows once, grows, and is an
    estimate step from then on."""
    db, model = _bench_fraud(shrink=200)
    item = db.tables["item"].to_numpy()
    dup = {c: v[:1] for c, v in item.items()}          # i_item_sk repeated
    db.insert_rows("item", **dup)
    st = db.stats["item"]
    assert st.unique("i_item_sk") and not st.estimated
    comp = PipelineCompiler()
    engine = ExtractionEngine(db, compiler=comp)
    got = engine.extract(model)
    assert comp.stats["retries"] == 1
    eager = ExtractionEngine(db, compiled=False).extract(model)
    assert _digests(got.edges) == _digests(eager.edges)
    prog = next(p for p in _programs_run(engine) if p.kind == "merged")
    assert prog.sizing[0] == ESTIMATE
    assert prog.capacities[0] & (prog.capacities[0] - 1) == 0
    retries = comp.stats["retries"]
    engine.extract(model)
    assert comp.stats["retries"] == retries


def test_memoized_program_follows_a_grown_fact_table():
    db, model = _bench_fraud(shrink=200)
    comp = PipelineCompiler()
    engine = ExtractionEngine(db, compiler=comp)
    engine.extract(model)
    before = db.tables["store_sales"].capacity
    sales = db.tables["store_sales"].to_numpy()
    db.insert_rows("store_sales", **sales)             # every row twice
    grown = db.tables["store_sales"].capacity
    assert grown > 2 * before - 1
    memo = {k: p.capacities for k, p in comp._unit_memo.items()}
    got = engine.extract(model)
    assert comp.stats["retries"] == 0
    # the memo kept what it learned at the old size; the run re-bound it
    assert all(c == (before,) * 3 for c in memo.values())
    stored = next(p for p in _programs_run(engine) if p.kind == "merged")
    prog, _ = comp.peek_program(db, "merged", stored.unit)
    assert prog.capacities == (grown,) * 3
    eager = ExtractionEngine(db, compiled=False).extract(model)
    assert _digests(got.edges) == _digests(eager.edges)


def test_capacity_counters_count_one_extract(dblp_db, monkeypatch):
    from repro import obs
    from repro.obs.metrics import MetricsRegistry

    monkeypatch.setattr(obs, "REGISTRY", MetricsRegistry())
    engine = ExtractionEngine(dblp_db.snapshot(),
                              compiler=PipelineCompiler())
    report = engine.explain_analyze(dblp_model(), method="extgraph-oj")
    steps = [s for u in list(report.views) + list(report.units)
             for s in u.steps]
    assert {s.sizing for s in steps} == {ESTIMATE, PROBE_BOUND}

    def value(name, **labels):
        return obs.REGISTRY.value(name, **labels)

    for sizing in (ESTIMATE, PROBE_BOUND):
        assert value("pipeline_capacity_steps_total", sizing=sizing) == sum(
            s.sizing == sizing for s in steps)
    assert value("pipeline_capacity_rows_total", rows="used") == sum(
        s.actual_rows for s in steps)
    assert value("pipeline_capacity_rows_total", rows="allotted") == sum(
        s.capacity for s in steps)


# -- range probes: a table of prefix counts over the build key's range -------

def _probe_rows(method):
    from repro import obs

    return obs.REGISTRY.value("pipeline_probe_rows_total", method=method)


def _item_row(db, key):
    """One ``item`` row, a copy of the first, keyed ``key``."""
    row = {c: v[:1].copy() for c, v in db.tables["item"].to_numpy().items()}
    row["i_item_sk"][:] = key
    return row


def _keyed_sales(db):
    """Probe rows with a key of the fraud unit's step into ``item``."""
    sales = db.tables["store_sales"].to_numpy()["ss_item_sk"]
    return int((sales != np.iinfo(np.int32).max).sum())


def test_stale_range_falls_back_to_the_bisection(monkeypatch):
    """A build key inserted beyond the planned range (stats not
    re-analyzed, the memoized program kept) is counted on the device: the
    unit re-runs once with that step bisecting, to the exact bag, and the
    step's probe rows count as searched."""
    from repro import obs
    from repro.obs.metrics import MetricsRegistry

    monkeypatch.setattr(obs, "REGISTRY", MetricsRegistry())
    db, model = _bench_fraud(shrink=200)
    comp = PipelineCompiler()
    engine = ExtractionEngine(db, compiler=comp)
    engine.extract(model)
    prog = next(p for p in _programs_run(engine) if p.kind == "merged")
    assert None not in prog.step_ranges()
    assert _probe_rows("search") == 0 and _probe_rows("range") > 0
    kmin, span = prog.ranges[0]                       # the step into item
    db.insert_rows("item", **_item_row(db, kmin + span))
    rows = {m: _probe_rows(m) for m in ("range", "search")}
    got = engine.extract(model)
    assert comp.stats["retries"] == 1
    eager = ExtractionEngine(db, compiled=False).extract(model)
    assert _digests(got.edges) == _digests(eager.edges)
    assert _probe_rows("search") == _keyed_sales(db)
    assert _probe_rows("range") > rows["range"]
    kept = comp._unit_memo[("merged", prog.unit)]
    assert kept.ranges == (None,) + prog.ranges[1:]
    engine.extract(model)
    assert comp.stats["retries"] == 1


def test_sparse_key_keeps_the_bisection(monkeypatch):
    """A build key whose span is over RANGE_SPAN_FACTOR times the step's
    rows bisects from the start: no retry, its rows count as searched."""
    from repro import obs
    from repro.core.pipeline import RANGE_SPAN_FACTOR
    from repro.obs.metrics import MetricsRegistry

    monkeypatch.setattr(obs, "REGISTRY", MetricsRegistry())
    db, model = _bench_fraud(shrink=200)
    far = RANGE_SPAN_FACTOR * db.tables["store_sales"].capacity + 10
    db.insert_rows("item", **_item_row(db, far))
    comp = PipelineCompiler()
    engine = ExtractionEngine(db, compiler=comp)
    got = engine.extract(model)
    prog = next(p for p in _programs_run(engine) if p.kind == "merged")
    assert prog.ranges[0] is None and None not in prog.ranges[1:]
    assert comp.stats["retries"] == 0
    assert _probe_rows("search") == _keyed_sales(db)
    assert _probe_rows("range") > 0
    eager = ExtractionEngine(db, compiled=False).extract(model)
    assert _digests(got.edges) == _digests(eager.edges)
