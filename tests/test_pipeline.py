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
from repro.core.pipeline import (
    PipelineCompiler,
    build_query_program,
    clear_executable_cache,
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
    prog = build_query_program(
        tpcds_db, fraud_model("store").queries()[0], edges=True)
    assert prog.kind == "edges"
    assert len(prog.capacities) == 2          # two joins in a 3-table chain
    for cap in prog.capacities:
        assert cap >= 8 and (cap & (cap - 1)) == 0, cap


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
