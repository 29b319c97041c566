"""Incremental maintenance: change capture, delta propagation, refresh().

The load-bearing contract is *parity*: whatever path serves a request —
cold extract, noop, delta propagation (including through maintained JS-MV
views and the kernel/bloom probe path), or full fallback — the bag digests
of every vertex/edge table must be bit-identical to a from-scratch extract
over the mutated database.
"""
import dataclasses

import numpy as np
import pytest

from repro.api import ExtractionEngine
from repro.core.database import Database, compute_stats
from repro.core.pipeline import PipelineCompiler
from repro.data import make_dblp, make_imdb, make_tpcds
from repro.data.dblp import dblp_model
from repro.data.imdb import imdb_model
from repro.data.tpcds import fraud_model, recommendation_model
from repro.incremental.changelog import ChangeLog, TableDelta, merge_deltas
from repro.incremental.delta import (
    DeltaExecutor,
    apply_table_delta,
    query_delta_terms,
)
from repro.relational import Table, bag_cancel_mask, subtract_bag
from repro.relational.ops import table_digest


def _digests(tables):
    return {k: table_digest(v) for k, v in tables.items()}


def _graph_digests(graph):
    return (_digests(graph.vertices), _digests(graph.edges))


def _oracle(db, model, method="extgraph"):
    """From-scratch extraction over the current table contents."""
    return ExtractionEngine(Database(dict(db.tables))).extract(
        model, method=method)


def _churn_tpcds(db, rng, n_ins=12, n_del=9, table="store_sales"):
    n = int(np.asarray(db.tables[table]["rid"]).max()) + 1
    db.insert_rows(
        table,
        rid=np.arange(n, n + n_ins, dtype=np.int32),
        c_sk=rng.integers(0, db.stats["customer"].rows, n_ins).astype(np.int32),
        i_sk=rng.integers(0, db.stats["item"].rows, n_ins).astype(np.int32),
        p_sk=rng.integers(0, db.stats["promotion"].rows, n_ins).astype(np.int32),
        o_sk=rng.integers(0, 4, n_ins).astype(np.int32))
    if n_del:
        live = np.flatnonzero(np.asarray(db.tables[table].valid))
        mask = np.zeros(db.tables[table].capacity, dtype=bool)
        mask[rng.choice(live, n_del, replace=False)] = True
        db.delete_rows(table, mask)


# ---------------------------------------------------------------------------
# bag algebra
# ---------------------------------------------------------------------------

def test_bag_cancel_mask_multiplicity():
    main = [np.array([1, 1, 1, 2, 2, 3], np.int32)]
    valid = np.ones(6, dtype=bool)
    keep = bag_cancel_mask(main, valid, [np.array([1, 1, 2], np.int32)])
    # exactly one 1, one 2, and the 3 survive
    survivors = sorted(main[0][keep].tolist())
    assert survivors == [1, 2, 3]


def test_bag_cancel_mask_respects_validity_and_missing_keys():
    main = [np.array([5, 5, 7], np.int32)]
    valid = np.array([True, False, True])
    # minus: one valid 5, one invalid 5 (ignored), one 9 (no match)
    keep = bag_cancel_mask(main, valid,
                           [np.array([5, 5, 9], np.int32)],
                           np.array([True, False, True]))
    assert keep.tolist() == [False, False, True]


def test_bag_cancel_mask_multi_column():
    src = np.array([1, 1, 1, 2], np.int32)
    dst = np.array([7, 7, 8, 7], np.int32)
    keep = bag_cancel_mask([src, dst], np.ones(4, bool),
                           [np.array([1], np.int32),
                            np.array([7], np.int32)])
    assert int(keep.sum()) == 3
    # the cancelled row is one of the (1, 7) duplicates, never (1, 8)/(2, 7)
    assert keep[2] and keep[3]


def test_subtract_bag_table():
    t = Table.from_arrays(a=np.array([1, 1, 2], np.int32),
                          b=np.array([10, 10, 20], np.int32))
    m = Table.from_arrays(a=np.array([1], np.int32),
                          b=np.array([10], np.int32))
    out = subtract_bag(t, m)
    assert sorted(out.to_rowset(["a", "b"])) == [(1, 10, 0), (2, 20, 0)]


def test_apply_table_delta_annihilation_and_bucketing():
    t = Table.from_arrays(src=np.array([1, 2], np.int32),
                          dst=np.array([5, 6], np.int32))
    plus = Table.from_arrays(src=np.array([3], np.int32),
                             dst=np.array([7], np.int32))
    # minus cancels a row that only exists via plus (insert-then-delete)
    minus = Table.from_arrays(src=np.array([3, 1], np.int32),
                              dst=np.array([7, 5], np.int32))
    out = apply_table_delta(t, [plus], [minus])
    assert sorted(out.to_rowset(["src", "dst"])) == [(2, 6, 0)]
    assert out.capacity == 8  # pow-2 bucket


# ---------------------------------------------------------------------------
# change capture
# ---------------------------------------------------------------------------

def test_mutation_api_updates_stats_incrementally():
    db = Database({"t": Table.from_arrays(
        rid=np.arange(10, dtype=np.int32),
        k=(np.arange(10, dtype=np.int32) % 3))})
    fp0 = db.fingerprint()
    db.insert_rows("t", rid=np.array([100, 101], np.int32),
                   k=np.array([7, 7], np.int32))
    st = db.stats["t"]
    assert st.rows == 12
    assert st.minmax["rid"] == (0, 101)       # merged min/max
    assert st.minmax["k"] == (0, 7)
    assert st.distinct["k"] <= 12             # approximate NDV, bounded
    assert db.fingerprint() != fp0            # mutations move the digest
    assert db.epoch == 1
    db.delete_where("t", "k", "==", 7)
    assert db.stats["t"].rows == 10
    assert db.epoch == 2
    log = db.changelog["t"]
    assert [e.epoch for e in log.entries] == [1, 2]
    assert log.entries[1].minus_count == 2
    # exact re-ANALYZE resets the approximation
    st = db.analyze("t")
    assert st == compute_stats(db.tables["t"])


def test_delete_to_empty_clears_minmax_and_ndv():
    # delete-only deltas must not leave stale minmax: an emptied table's
    # old range bounds nothing (discovery profiles read minmax as a
    # range-fit signal, so a stale (0, 5) on an empty-then-refilled table
    # would mis-score every FK candidate against it)
    db = Database({"t": Table.from_arrays(
        rid=np.arange(6, dtype=np.int32),
        k=(np.arange(6, dtype=np.int32) % 2))})
    db.delete_rows("t", np.arange(6))
    st = db.stats["t"]
    assert st.rows == 0
    assert st.minmax == {}
    assert set(st.distinct) == {"rid", "k"}
    assert all(n == 0 for n in st.distinct.values())
    # a later insert re-seeds both from the inserted rows alone
    db.insert_rows("t", rid=np.array([50, 51], np.int32),
                   k=np.array([9, 9], np.int32))
    st = db.stats["t"]
    assert st.rows == 2
    assert st.minmax["rid"] == (50, 51)
    assert st.minmax["k"] == (9, 9)
    assert st.distinct["rid"] == 2
    assert st.distinct["k"] == 1


def test_incremental_ndv_bounded_under_mixed_churn():
    # the approximation may drift, but must keep its contract: NDV in
    # [1, rows] per column, and minmax a conservative superset of the
    # true range — the invariants the cost model and discovery rely on
    rng = np.random.default_rng(0)
    db = Database({"t": Table.from_arrays(
        rid=np.arange(256, dtype=np.int32),
        k=rng.integers(0, 32, 256).astype(np.int32))})
    next_rid = 256
    for _ in range(12):
        n = 16
        db.insert_rows(
            "t", rid=np.arange(next_rid, next_rid + n, dtype=np.int32),
            k=rng.integers(0, 32, n).astype(np.int32))
        next_rid += n
        live = np.flatnonzero(np.asarray(db.tables["t"].valid))
        mask = np.zeros(db.tables["t"].capacity, dtype=bool)
        mask[rng.choice(live, n, replace=False)] = True
        db.delete_rows("t", mask)
    st = db.stats["t"]
    exact = compute_stats(db.tables["t"])
    assert st.rows == exact.rows == 256
    for c in ("rid", "k"):
        assert 1 <= st.distinct[c] <= st.rows
        assert st.minmax[c][0] <= exact.minmax[c][0]
        assert st.minmax[c][1] >= exact.minmax[c][1]
    # the low-cardinality column's estimate stays the right order of
    # magnitude (true NDV 32): uniform-deletion scaling must not collapse
    # it to 1 or inflate it toward the row count
    assert exact.distinct["k"] // 4 <= st.distinct["k"] \
        <= 4 * exact.distinct["k"]


def test_rows_like_minus_bag_cancels():
    db = Database({"t": Table.from_arrays(
        rid=np.array([1, 1, 2], np.int32))})
    db.apply_delta("t", minus={"rid": np.array([1], np.int32)})
    assert db.stats["t"].rows == 2
    rows = sorted(r[0] for r in db.tables["t"].to_rowset(["rid"]))
    assert rows == [1, 2]


def test_rows_like_minus_logs_only_actual_deletions():
    db = Database({"t": Table.from_arrays(
        rid=np.array([1, 2], np.int32))})
    # one real match (1), one phantom (99): only the match may be logged,
    # or refresh()'s minus terms would cancel edges that never existed
    entry = db.apply_delta("t", minus={"rid": np.array([1, 99], np.int32)})
    assert entry.minus_count == 1
    assert sorted(r[0] for r in entry.minus.to_rowset(["rid"])) == [1]
    assert db.stats["t"].rows == 1


def test_delete_rows_accepts_indices_and_rejects_junk():
    db = Database({"t": Table.from_arrays(rid=np.arange(6, dtype=np.int32))})
    db.delete_rows("t", np.array([1, 4]))
    assert db.stats["t"].rows == 4
    assert sorted(r[0] for r in db.tables["t"].to_rowset(["rid"])) == \
        [0, 2, 3, 5]
    with pytest.raises(ValueError, match="bool mask or integer"):
        db.delete_rows("t", np.array([0.5]))


def test_view_staleness_uses_changelog_not_fingerprint(monkeypatch):
    # an insert+delete round can net stats back to an identical
    # fingerprint; the changelog epoch must still flag the view stale
    db = make_tpcds(sf=1, seed=0)
    engine = ExtractionEngine(db, auto_refresh=True)
    model = recommendation_model("store")
    first = engine.extract(model)
    if not first.provenance.views_built:
        pytest.skip("plan built no views")
    rng = np.random.default_rng(11)
    _churn_tpcds(db, rng, n_ins=6, n_del=6)
    # simulate the fingerprint collision: overwrite the stored digests
    # with the post-mutation ones, so only the changelog can tell
    # (cache entries are frozen — replace them, as refresh itself does)
    for sig, cv in list(engine._views.items()):
        cv = dataclasses.replace(cv, base_fingerprints={
            t: engine._table_fingerprint(t) for t in cv.base_fingerprints})
        engine._views.put(sig, cv)
        assert engine._view_bases_mutated(cv)   # epoch signal still fires
    r = engine.extract(model)
    assert r.refresh.path == "delta"
    assert r.refresh.views_maintained          # maintained despite collision
    assert _graph_digests(r.graph) == \
        _graph_digests(_oracle(db, model).graph)


def test_snapshot_isolation_under_mutation():
    db = make_tpcds(sf=1, seed=0)
    rng = np.random.default_rng(0)
    _churn_tpcds(db, rng)                     # pre-snapshot history
    snap = db.snapshot()
    fp = snap.fingerprint()
    rows_before = snap.stats["store_sales"].rows
    log_len = len(snap.changelog["store_sales"].entries)

    # mutate the parent: the snapshot must not move
    _churn_tpcds(db, rng)
    db.delete_where("customer", "c_id", "<", 5)
    assert snap.epoch != db.epoch
    assert snap.fingerprint() == fp
    assert snap.stats["store_sales"].rows == rows_before
    assert len(snap.changelog["store_sales"].entries) == log_len
    assert int(np.asarray(snap.tables["customer"].valid).sum()) == \
        snap.stats["customer"].rows

    # and the other direction: snapshot mutations never reach the parent
    parent_fp = db.fingerprint()
    parent_epoch = db.epoch
    snap.delete_where("item", "i_id", "<", 3)
    assert db.fingerprint() == parent_fp
    assert db.epoch == parent_epoch
    assert "item" not in db.changelog


def test_changelog_prune_and_wholesale_replace():
    db = Database({"t": Table.from_arrays(rid=np.arange(4, dtype=np.int32))})
    db.insert_rows("t", rid=np.array([10], np.int32))
    db.insert_rows("t", rid=np.array([11], np.int32))
    assert db.covers_epoch("t", 0)
    assert db.changelog["t"].rows_changed_since(0) == 2
    db.prune_changelog(1)
    assert not db.covers_epoch("t", 0)        # history below 1 is gone
    assert db.covers_epoch("t", 1)
    assert len(db.deltas_since("t", 0)) == 1
    # wholesale replacement invalidates every older cursor
    epoch = db.epoch
    db.add_table("t", Table.from_arrays(rid=np.arange(2, dtype=np.int32)))
    assert not db.covers_epoch("t", epoch)
    assert db.covers_epoch("t", db.epoch)


def test_merge_deltas_folds_entries():
    p1 = Table.from_arrays(a=np.array([1], np.int32))
    p2 = Table.from_arrays(a=np.array([2, 3], np.int32))
    m1 = Table.from_arrays(a=np.array([9], np.int32))
    merged = merge_deltas([
        TableDelta(epoch=1, plus=p1, plus_count=1),
        TableDelta(epoch=2, plus=p2, minus=m1, plus_count=2, minus_count=1),
    ])
    assert merged.plus_count == 3 and merged.minus_count == 1
    assert sorted(r[0] for r in merged.plus.to_rowset(["a"])) == [1, 2, 3]
    assert merged.plus.capacity == 8          # pow-2 padded (min bucket)


# ---------------------------------------------------------------------------
# delta terms
# ---------------------------------------------------------------------------

def test_query_delta_terms_versions():
    from repro.data.tpcds import copur_query
    q = copur_query("store")                  # F1/F2 both read store_sales
    terms = query_delta_terms(q, {"store_sales"})
    assert len(terms) == 4                    # 2 occurrences x 2 signs
    by_alias = {}
    for t in terms:
        by_alias.setdefault(t.delta_alias, []).append(t)
        tables = {r.alias: r.table for r in t.query.relations}
        assert tables[t.delta_alias] == "store_sales#delta"
    # the F1 term reads F2 old; the F2 term reads F1 new
    f1 = by_alias["F1"][0].query
    assert {r.alias: r.table for r in f1.relations}["F2"] == "store_sales#old"
    f2 = by_alias["F2"][0].query
    assert {r.alias: r.table for r in f2.relations}["F1"] == "store_sales#new"
    # unchanged tables bind the canonical #new name
    assert {r.alias: r.table for r in f1.relations}["I"] == "item#new"


# ---------------------------------------------------------------------------
# refresh parity (the acceptance contract)
# ---------------------------------------------------------------------------

def _scripted_churn_parity(db, model, churn_rounds, engine=None):
    engine = engine or ExtractionEngine(db, auto_refresh=True)
    r = engine.extract(model)
    assert r.refresh.path == "cold"
    paths = []
    for mutate in churn_rounds:
        mutate(db)
        r = engine.extract(model)
        paths.append(r.refresh.path)
        oracle = _oracle(db, model)
        assert _graph_digests(r.graph) == _graph_digests(oracle.graph), \
            f"digest divergence on path {r.refresh.path}"
    return engine, paths


def test_refresh_parity_tpcds_fraud():
    db = make_tpcds(sf=1, seed=0)
    rng = np.random.default_rng(1)
    engine, paths = _scripted_churn_parity(db, fraud_model("store"), [
        lambda d: _churn_tpcds(d, rng, n_ins=10, n_del=0),   # inserts
        lambda d: _churn_tpcds(d, rng, n_ins=0, n_del=8),    # deletes
        lambda d: _churn_tpcds(d, rng, n_ins=10, n_del=8),   # mixed
        # dimension churn: new items shift the vertex set too
        lambda d: d.insert_rows(
            "item",
            rid=np.arange(10_000, 10_003, dtype=np.int32),
            i_id=np.arange(10_000, 10_003, dtype=np.int32),
            i_price=np.array([1, 2, 3], np.int32)),
    ])
    assert paths == ["delta"] * 4
    # a second engine pays cold; this one served every round incrementally
    assert engine.cache_info()["results"] == 1


@pytest.mark.slow
def test_refresh_parity_dblp_through_maintained_views():
    db = make_dblp(scale=1, seed=1)
    engine = ExtractionEngine(db, auto_refresh=True)
    model = dblp_model()
    first = engine.extract(model)
    assert first.refresh.path == "cold"
    rng = np.random.default_rng(2)

    def churn_wrote(d):
        n = int(np.asarray(d.tables["wrote"]["rid"]).max()) + 1
        d.insert_rows(
            "wrote",
            rid=np.arange(n, n + 25, dtype=np.int32),
            a_sk=rng.integers(0, d.stats["author"].rows, 25).astype(np.int32),
            p_sk=rng.integers(0, d.stats["paper"].rows, 25).astype(np.int32))
        live = np.flatnonzero(np.asarray(d.tables["wrote"].valid))
        mask = np.zeros(d.tables["wrote"].capacity, dtype=bool)
        mask[rng.choice(live, 20, replace=False)] = True
        d.delete_rows("wrote", mask)

    churn_wrote(db)
    r = engine.extract(model)
    assert r.refresh.path == "delta"
    oracle = _oracle(db, model)
    assert _graph_digests(r.graph) == _graph_digests(oracle.graph)

    # if the plan materialized views, they must have been maintained in
    # place — and their content must equal a fresh materialization
    if first.provenance.views_built:
        assert r.refresh.views_maintained
        from repro.core.executor import execute_query
        for sig, cv in engine._views.items():
            from repro.core.jsmv import ViewDef
            fresh = execute_query(Database(dict(db.tables)),
                                  ViewDef(cv.name, cv.pattern).as_query())
            assert table_digest(cv.table) == table_digest(fresh)

        # a follow-up request that *reads* the maintained views (fresh
        # plan, cached views adopted as free JS-MV rewrites): still exact
        r2 = engine.extract(model, method="extgraph-mv", auto_refresh=False)
        assert _graph_digests(r2.graph) == _graph_digests(
            _oracle(db, model, method="extgraph-mv").graph)


@pytest.mark.slow
def test_refresh_parity_imdb():
    db = make_imdb(scale=1, seed=2)
    rng = np.random.default_rng(3)

    def churn_directs(d):
        n = int(np.asarray(d.tables["directs"]["rid"]).max()) + 1
        d.insert_rows(
            "directs",
            rid=np.arange(n, n + 15, dtype=np.int32),
            per_sk=rng.integers(0, d.stats["person"].rows, 15).astype(np.int32),
            m_sk=rng.integers(0, d.stats["movie"].rows, 15).astype(np.int32))

    def delete_acts(d):
        live = np.flatnonzero(np.asarray(d.tables["acts"].valid))
        mask = np.zeros(d.tables["acts"].capacity, dtype=bool)
        mask[rng.choice(live, 30, replace=False)] = True
        d.delete_rows("acts", mask)

    _, paths = _scripted_churn_parity(db, imdb_model(),
                                      [churn_directs, delete_acts])
    assert paths == ["delta", "delta"]


@pytest.mark.slow
def test_refresh_parity_kernel_and_bloom_path():
    db = make_tpcds(sf=1, seed=4)
    compiler = PipelineCompiler(use_kernel=True, use_bloom=True)
    engine = ExtractionEngine(db, compiler=compiler, auto_refresh=True)
    model = fraud_model("store")
    engine.extract(model)
    rng = np.random.default_rng(5)
    _churn_tpcds(db, rng)
    r = engine.extract(model)
    assert r.refresh.path == "delta"
    oracle = _oracle(db, model)
    assert _graph_digests(r.graph) == _graph_digests(oracle.graph)


def test_refresh_after_inserts_beyond_the_planned_key_ranges():
    """Items keyed beyond every range the programs planned, and sales of
    them: each delta refresh is exact.  The second round's delta terms
    run the programs memoized in the first, whose ranges cover the first
    round's keys only: their stale steps re-run by bisection."""
    db = make_tpcds(sf=1, seed=0)
    compiler = PipelineCompiler()
    engine = ExtractionEngine(db, compiler=compiler, auto_refresh=True)
    model = fraud_model("store")
    engine.extract(model)

    def one(v):
        return np.array([v], np.int32)

    for _ in range(2):
        key = db.stats["item"].minmax["i_id"][1] + 3000
        rid = db.stats["store_sales"].minmax["rid"][1] + 1
        db.insert_rows("item", rid=one(key), i_id=one(key), i_price=one(1))
        db.insert_rows("store_sales", rid=one(rid), c_sk=one(3),
                       i_sk=one(key), p_sk=one(0), o_sk=one(1))
        r = engine.extract(model)
        assert r.refresh.path == "delta"
        assert _graph_digests(r.graph) == \
            _graph_digests(_oracle(db, model).graph)
    assert compiler.stats["retries"] > 0


def test_refresh_paths_noop_threshold_and_fallbacks():
    db = make_tpcds(sf=1, seed=0)
    engine = ExtractionEngine(db, refresh_threshold=0.05)
    model = fraud_model("store")
    assert engine.refresh(model).refresh.path == "cold"
    assert engine.refresh(model).refresh.path == "noop"

    rng = np.random.default_rng(6)
    _churn_tpcds(db, rng, n_ins=5, n_del=0)
    r = engine.refresh(model)
    assert r.refresh.path == "delta"
    assert 0.0 < r.refresh.churn <= 0.05
    assert r.refresh.epoch_to == db.epoch
    assert "store_sales" in r.refresh.tables_changed
    # the delta path re-keys the cached plan under the mutated stats, so a
    # plain (non-refresh) extract right after still hits the plan cache —
    # and the stale slot is dropped rather than left to crowd the LRU
    assert engine.extract(model).provenance.plan_cache_hit
    assert engine.cache_info()["plans"] == 1

    # churn above the threshold falls back to the full path — still exact
    _churn_tpcds(db, rng, n_ins=600, n_del=0)
    r = engine.refresh(model)
    assert r.refresh.path == "full"
    assert r.refresh.churn > 0.05
    assert _graph_digests(r.graph) == \
        _graph_digests(_oracle(db, model).graph)

    # wholesale table replacement breaks the changelog: full path again
    fresh = make_tpcds(sf=1, seed=9)
    db.add_table("store_sales", fresh.table("store_sales"))
    r = engine.refresh(model)
    assert r.refresh.path == "full"
    assert _graph_digests(r.graph) == \
        _graph_digests(_oracle(db, model).graph)

    # refresh is a planned-method affair
    with pytest.raises(ValueError):
        engine.refresh(model, method="ringo")


def test_vertex_only_churn_stays_delta_and_exact():
    db = make_tpcds(sf=1, seed=0)
    engine = ExtractionEngine(db, auto_refresh=True)
    model = fraud_model("store")
    engine.extract(model)
    # new customers that no sale references: edges unchanged, vertices not
    db.insert_rows("customer",
                   rid=np.array([90_000], np.int32),
                   c_id=np.array([90_000], np.int32),
                   c_prop=np.array([1], np.int32))
    r = engine.extract(model)
    assert r.refresh.path == "delta"
    assert _graph_digests(r.graph) == \
        _graph_digests(_oracle(db, model).graph)


# ---------------------------------------------------------------------------
# over-invalidation regressions (plan cache + view cache)
# ---------------------------------------------------------------------------

def test_unrelated_churn_keeps_plan_and_views():
    db = make_tpcds(sf=1, seed=0)
    engine = ExtractionEngine(db)
    model = recommendation_model("store")
    first = engine.extract(model)
    assert first.provenance.views_built
    n_views = engine.cache_info()["views"]

    # churn a table the model never reads: web_sales
    rng = np.random.default_rng(7)
    _churn_tpcds(db, rng, table="web_sales")

    after = engine.extract(model)
    # regression: the full-catalog fingerprint used to miss here, forcing
    # a several-second replan; the view cache must survive too
    assert after.provenance.plan_cache_hit
    assert set(after.provenance.views_reused) == \
        set(first.provenance.views_built)
    assert not after.provenance.views_built
    assert engine.cache_info()["views"] == n_views

    # related churn still invalidates (the eviction is per base table)
    _churn_tpcds(db, rng, table="store_sales", n_ins=5, n_del=0)
    related = engine.extract(model)
    assert not related.provenance.plan_cache_hit
    assert _graph_digests(related.graph) == \
        _graph_digests(_oracle(db, model).graph)


def test_auto_refresh_unrelated_churn_is_noop():
    db = make_tpcds(sf=1, seed=0)
    engine = ExtractionEngine(db, auto_refresh=True)
    model = fraud_model("store")
    engine.extract(model)
    rng = np.random.default_rng(8)
    _churn_tpcds(db, rng, table="catalog_sales")
    r = engine.extract(model)
    assert r.refresh.path == "noop"


# ---------------------------------------------------------------------------
# CSR patching
# ---------------------------------------------------------------------------

def _coo_counter(csr, label):
    import collections
    src = np.asarray(csr.sources[label])
    dst = np.asarray(csr.targets[label])
    valid = np.asarray(csr.edge_valid(label))
    return collections.Counter(zip(src[valid].tolist(), dst[valid].tolist()))


def test_csr_apply_edge_delta_tombstones_and_compaction():
    import collections

    db = make_tpcds(sf=1, seed=0)
    engine = ExtractionEngine(db)
    model = fraud_model("store")
    csr = engine.extract(model).graph_view()
    label = "Buy"
    before = _coo_counter(csr, label)

    # delete two existing edges (one duplicated pair), add three
    src = np.asarray(csr.sources[label])
    dst = np.asarray(csr.targets[label])
    valid = np.asarray(csr.edge_valid(label))
    i0, i1 = np.flatnonzero(valid)[:2]
    del_src = np.array([src[i0], src[i1]], np.int32)
    del_dst = np.array([dst[i0], dst[i1]], np.int32)
    lo_c, hi_c = csr.vertex_ranges["Customer"]
    lo_i, hi_i = csr.vertex_ranges["Item"]
    add_src = np.array([lo_c, lo_c, hi_c - 1], np.int32)
    add_dst = np.array([lo_i, hi_i - 1, lo_i], np.int32)

    patched = csr.apply_edge_delta(label, add_src, add_dst,
                                   del_src, del_dst)
    assert label in patched.dirty             # offsets stale, COO exact
    expected = collections.Counter(before)
    expected.subtract(collections.Counter(
        zip(del_src.tolist(), del_dst.tolist())))
    expected.update(zip(add_src.tolist(), add_dst.tolist()))
    expected = +expected
    assert _coo_counter(patched, label) == expected
    assert patched.edge_counts[label] == sum(expected.values())
    # out_degree falls back to a histogram on the dirty label
    deg = np.asarray(patched.out_degree(label))
    ref = np.zeros(patched.num_vertices, np.int64)
    for (s, _), c in expected.items():
        ref[s] += c
    assert (deg == ref).all()
    # other labels still share clean offsets
    assert "Sell" not in patched.dirty

    # threshold 0 forces compaction: clean CSR, same multiset
    compacted = patched.apply_edge_delta(
        label, del_src=np.array([add_src[0]], np.int32),
        del_dst=np.array([add_dst[0]], np.int32), compact_threshold=0.0)
    assert label not in compacted.dirty
    expected.subtract({(int(add_src[0]), int(add_dst[0])): 1})
    assert _coo_counter(compacted, label) == +expected
    off = np.asarray(compacted.offsets[label])
    deg2 = np.asarray(compacted.out_degree(label))
    assert (off[1:] - off[:-1] == deg2).all()


def test_engine_refresh_patches_cached_csr():
    db = make_tpcds(sf=1, seed=0)
    engine = ExtractionEngine(db, auto_refresh=True)
    model = fraud_model("store")
    cold = engine.analyze(model, algorithm="pagerank", label="Buy", iters=8)
    rng = np.random.default_rng(9)
    _churn_tpcds(db, rng, n_ins=8, n_del=6)

    warm = engine.analyze(model, algorithm="pagerank", label="Buy", iters=8)
    assert warm.extraction.refresh.path == "delta"
    assert warm.extraction.refresh.csr_patched
    assert warm.provenance.csr_cache_hit      # the patched CSR served it
    assert warm.provenance.csr_key != cold.provenance.csr_key

    oracle_engine = ExtractionEngine(Database(dict(db.tables)))
    oracle = oracle_engine.analyze(model, algorithm="pagerank",
                                   label="Buy", iters=8)
    np.testing.assert_allclose(np.asarray(warm.values),
                               np.asarray(oracle.values),
                               rtol=1e-5, atol=1e-7)
    # exact algorithms agree exactly on the patched CSR
    wcc_warm = engine.analyze(model, algorithm="wcc")
    wcc_oracle = oracle_engine.analyze(model, algorithm="wcc")
    assert (np.asarray(wcc_warm.values) ==
            np.asarray(wcc_oracle.values)).all()
