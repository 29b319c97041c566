"""ExtractionEngine / builder / spec-loader coverage.

The engine must (a) return exactly what the one-shot path returns, (b) hit
its plan cache on a repeated model signature, (c) reuse cached JS-MV views
across requests, and (d) drop cached state when ANALYZE stats change.
"""
import dataclasses

import numpy as np
import pytest

from repro.api import (
    ExtractionEngine,
    GraphModelBuilder,
    join_query,
    model_from_json,
    model_from_spec,
    model_to_spec,
)
from repro.core import (
    ColumnRef,
    EdgeDef,
    GraphModel,
    JoinCond,
    JoinQuery,
    Predicate,
    Relation,
    VertexDef,
    extract_graph,
    query_signature,
)
from repro.core.pipeline import clear_executable_cache
from repro.data import make_tpcds, recommendation_model
from repro.data.tpcds import buy_query, fraud_model
from repro.relational import Table


def _edge_bags(edges):
    return {
        label: sorted(
            zip(t.to_numpy()["src"].tolist(), t.to_numpy()["dst"].tolist())
        )
        for label, t in edges.items()
    }


# ---------------------------------------------------------------------------
# Builder + spec loader
# ---------------------------------------------------------------------------

def test_builder_roundtrip_equals_hand_built():
    """Fluent construction reproduces the raw-dataclass model exactly."""
    hand = GraphModel(
        name="mini",
        vertices=(
            VertexDef("Customer", "customer", "c_id", ("c_prop",)),
            VertexDef("Item", "item", "i_id", ()),
        ),
        edges=(
            EdgeDef("Buy", "Customer", "Item", JoinQuery(
                name="Buy",
                relations=(Relation("C", "customer"),
                           Relation("F", "store_sales"),
                           Relation("I", "item", (Predicate("i_price", "<", 500.0),))),
                conds=(JoinCond("C", "c_id", "F", "c_sk"),
                       JoinCond("F", "i_sk", "I", "i_id")),
                src=ColumnRef("C", "c_id"),
                dst=ColumnRef("I", "i_id"),
            )),
        ),
    )
    built = (GraphModel.builder("mini")
             .vertex("Customer", table="customer", id_col="c_id",
                     props=("c_prop",))
             .vertex("Item", table="item", id_col="i_id")
             .edge("Buy", src="Customer", dst="Item",
                   relations=[("C", "customer"), ("F", "store_sales"),
                              ("I", "item", ["i_price < 500"])],
                   joins=["C.c_id == F.c_sk", "F.i_sk == I.i_id"])
             .build())
    assert built == hand


def test_builder_endpoint_inference_and_explicit_cols():
    """src/dst inferred from a unique table; self-joins need explicit refs."""
    b = (GraphModel.builder("m")
         .vertex("Customer", table="customer", id_col="c_id")
         .edge("Co-pur", src="Customer", dst="Customer",
               relations=[("C1", "customer"), ("F1", "store_sales"),
                          ("I", "item"), ("F2", "store_sales"),
                          ("C2", "customer")],
               joins=["C1.c_id == F1.c_sk", "F1.i_sk == I.i_id",
                      "I.i_id == F2.i_sk", "F2.c_sk == C2.c_id"],
               src_col="C1.c_id", dst_col="C2.c_id"))
    q = b.build().edge("Co-pur").query
    assert q.src == ColumnRef("C1", "c_id")
    assert q.dst == ColumnRef("C2", "c_id")

    # customer occurs twice: inference must refuse rather than guess
    with pytest.raises(ValueError, match="occurs 2x"):
        (GraphModel.builder("m")
         .vertex("Customer", table="customer", id_col="c_id")
         .edge("Co-pur", src="Customer", dst="Customer",
               relations=[("C1", "customer"), ("C2", "customer")],
               joins=["C1.c_id == C2.c_id"])
         .build())


def test_builder_validation_errors():
    with pytest.raises(ValueError, match="undeclared vertex"):
        (GraphModel.builder("m")
         .edge("E", src="Nope", dst="Nope", query=buy_query("store"))
         .build())
    with pytest.raises(ValueError, match="duplicate vertex"):
        (GraphModel.builder("m")
         .vertex("V", table="t", id_col="i")
         .vertex("V", table="t", id_col="i"))
    with pytest.raises(ValueError, match="exactly one of"):
        GraphModelBuilder("m").edge("E", src="A", dst="B")


def test_join_query_parsing_matches_dataclasses():
    q = join_query(
        "Buy",
        relations=[("C", "customer"), ("F", "web_sales"), ("I", "item")],
        joins=["C.c_id == F.c_sk", "F.i_sk == I.i_id"],
        src="C.c_id", dst="I.i_id")
    assert q == buy_query("web")


@pytest.mark.parametrize("model_fn", [
    lambda: recommendation_model("store"),
    lambda: fraud_model("catalog"),
])
def test_spec_roundtrip(model_fn):
    model = model_fn()
    spec = model_to_spec(model)
    assert model_from_spec(spec) == model
    import json
    assert model_from_json(json.dumps(spec)) == model


def test_query_signature_alias_independent():
    q1 = buy_query("store")
    renamed = JoinQuery(
        name="Buy",
        relations=(Relation("kunde", "customer"), Relation("fakt", "store_sales"),
                   Relation("ware", "item")),
        conds=(JoinCond("kunde", "c_id", "fakt", "c_sk"),
               JoinCond("fakt", "i_sk", "ware", "i_id")),
        src=ColumnRef("kunde", "c_id"),
        dst=ColumnRef("ware", "i_id"),
    )
    assert query_signature(q1) == query_signature(renamed)
    # different output column -> different signature
    other_out = dataclasses.replace(q1, dst=ColumnRef("I", "rid"))
    assert query_signature(q1) != query_signature(other_out)


# ---------------------------------------------------------------------------
# Engine caching behaviour
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def db():
    return make_tpcds(sf=1, seed=0)


def test_engine_caches_and_matches_wrapper(db):
    # the executable store is process-wide: start it empty, so that an
    # earlier test of the same workload cannot turn the cold misses to hits
    clear_executable_cache()
    engine = ExtractionEngine(db)
    model = recommendation_model("store")

    cold = engine.extract(model)
    assert not cold.provenance.plan_cache_hit
    assert cold.provenance.views_built, "expected JS-MV view(s) at SF=1"
    info = engine.cache_info()
    assert info["plans"] == 1
    assert info["views"] == len(cold.provenance.views_built)
    assert info["csrs"] == 0
    # cold request compiled its unit executables (no reuse yet)
    assert info["executable_misses"] > 0
    cold_misses = info["executable_misses"]

    # warm request: fresh (but signature-identical) model object
    warm = engine.extract(recommendation_model("store"))
    assert warm.provenance.plan_cache_hit
    assert warm.provenance.views_reused and not warm.provenance.views_built
    assert warm.timings.plan_s < cold.timings.plan_s
    # warm request replayed cached executables without re-tracing
    info = engine.cache_info()
    assert info["executable_hits"] > 0
    assert info["executable_misses"] == cold_misses

    # engine result == deprecated one-shot wrapper == ringo oracle
    with pytest.deprecated_call():
        wrapped, _ = extract_graph(db, model)
    with pytest.deprecated_call():
        oracle, _ = extract_graph(db, model, method="ringo")
    want = _edge_bags(oracle.edges)
    assert _edge_bags(cold.edges) == want
    assert _edge_bags(warm.edges) == want
    assert _edge_bags(wrapped.edges) == want

    # vertices ride along on every request
    assert set(cold.vertices) == {"Customer", "Item", "Promotion"}

    # per-request isolation: engine views never leak into the caller's db
    assert not any(n.startswith("view_") for n in db.tables)
    assert not any(n.startswith("view_") for n in db.stats)


def test_cross_model_view_reuse(db):
    """A view built for one model is a free MV candidate for the next."""
    from repro.core import plan_cost

    engine = ExtractionEngine(db, max_plans=1)
    first = engine.extract(recommendation_model("store"))
    assert first.provenance.views_built
    # fraud(store) embeds customer |><| store_sales once; the cached view is
    # free, so the planner adopts it even for a single use
    second = engine.extract(fraud_model("store"))
    assert not second.provenance.plan_cache_hit  # different model signature
    assert second.provenance.views_reused
    with pytest.deprecated_call():
        oracle, _ = extract_graph(db, fraud_model("store"), method="ringo")
    assert _edge_bags(second.edges) == _edge_bags(oracle.edges)
    # the public cost entry point handles plans with reused views (their
    # stats are estimated on the fly when absent from the caller's db)
    assert second.plan.reused
    assert plan_cost(db.snapshot(), second.plan) > 0
    # LRU bound: max_plans=1 means the recommendation plan was evicted
    assert engine.cache_info()["plans"] == 1


def test_parse_join_rejects_non_equijoin():
    with pytest.raises(ValueError, match="only equijoins"):
        join_query("Q", relations=[("A", "t"), ("B", "u")],
                   joins=["A.x != B.y"], src="A.x", dst="B.y")


def test_edge_name_override_with_query():
    q = buy_query("store")
    model = (GraphModel.builder("m")
             .vertex("Customer", table="customer", id_col="c_id")
             .vertex("Item", table="item", id_col="i_id")
             .edge("BuyAlt", src="Customer", dst="Item", query=q,
                   name="BuyAlt")
             .build())
    assert model.edge("BuyAlt").query.name == "BuyAlt"


def test_view_invalidation_after_analyze():
    db = make_tpcds(sf=1, seed=0)
    engine = ExtractionEngine(db)
    model = recommendation_model("store")
    first = engine.extract(model)
    assert first.provenance.views_built

    # replace the fact table's data (new rows) and re-ANALYZE: stats change,
    # so both the cached plan and the dependent view must be discarded
    fresh = make_tpcds(sf=1, seed=7)
    db.add_table("store_sales", fresh.table("store_sales"))
    after = engine.extract(model)
    assert not after.provenance.plan_cache_hit
    assert not after.provenance.views_reused
    assert after.provenance.views_built
    with pytest.deprecated_call():
        oracle, _ = extract_graph(db, model, method="ringo")
    assert _edge_bags(after.edges) == _edge_bags(oracle.edges)

    # re-ANALYZE with unchanged data leaves fingerprints (and caches) intact
    db.analyze("store_sales")
    again = engine.extract(model)
    assert again.provenance.plan_cache_hit
    assert again.provenance.views_reused


def test_database_snapshot_isolation():
    db = make_tpcds(sf=1, seed=0)
    snap = db.snapshot()
    snap.add_view("view_x", db.table("customer"), db.stats["customer"])
    snap.analyze("customer")
    assert "view_x" not in db.tables
    assert db.fingerprint() == make_tpcds(sf=1, seed=0).fingerprint()
