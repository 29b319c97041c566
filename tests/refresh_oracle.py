"""Refresh parity against a fresh extract and the numpy oracle: the
cases of ``test_refresh_oracle_*.py``.

An auto-refresh engine extracts a model cold, the database churns, and the
next extract is served by delta propagation.  Every round's graph must
digest the same as a from-scratch extract over the mutated tables, and its
edge bags and vertex ids must equal ``bench.reference``'s numpy equijoins.
Churn kinds: inserts only, deletes only, both, and a dimension key beyond
every range the programs planned with a fact row of it (two rounds: the
second round's delta terms run the programs memoized in the first, whose
ranges cover the first round's key only, so their stale steps re-run by
bisection).
"""
import functools

import numpy as np

from oracle import assert_edges_match, assert_vertices_match, edge_keys
from repro.api import ExtractionEngine
from repro.core.database import Database
from repro.core.executor import execute_query
from repro.core.jsmv import ViewDef
from repro.core.pipeline import PipelineCompiler
from repro.data import (
    combined_model,
    dblp_model,
    fraud_model,
    imdb_model,
    make_dblp,
    make_imdb,
    make_tpcds,
)
from repro.relational.ops import table_digest

# model -> (model, database, tables churned, the dimension a key beyond
# the planned ranges is inserted into)
MODELS = {
    "fraud_store": (lambda: fraud_model("store"),
                    lambda: make_tpcds(sf=1, seed=0), ("store_sales",),
                    "item"),
    "combined": (combined_model, lambda: make_tpcds(sf=1, seed=0),
                 ("store_sales", "catalog_sales"), "item"),
    "dblp": (dblp_model, lambda: make_dblp(scale=1, seed=1), ("wrote",),
             "author"),
    "imdb": (imdb_model, lambda: make_imdb(scale=1, seed=2),
             ("acts", "directs"), "person"),
}
KINDS = ("inserts", "deletes", "mixed", "beyond")


def _references(model, table):
    """``{column: (table, column)}``: what each of ``table``'s columns
    joins in the model's queries."""
    out = {}
    for q in model.queries():
        for c in q.conds:
            for a, ac, b, bc in ((c.left, c.lcol, c.right, c.rcol),
                                 (c.right, c.rcol, c.left, c.lcol)):
                if q.relation(a).table == table:
                    out[ac] = (q.relation(b).table, bc)
    return out


def _next_rid(db, table):
    return int(db.tables[table].to_numpy()["rid"].max()) + 1


def _insert(db, model, table, rng, n, fixed=None):
    """``n`` copies of random rows of ``table`` under new rids, each
    foreign key drawn from the keys it joins (``fixed`` pins some
    columns)."""
    data = db.tables[table].to_numpy()
    rows = {c: v[rng.integers(0, len(v), n)] for c, v in data.items()}
    rows["rid"] = np.arange(_next_rid(db, table), _next_rid(db, table) + n)
    for col, (dim, key) in _references(model, table).items():
        rows[col] = rng.choice(db.tables[dim].to_numpy()[key], n)
    rows.update(fixed or {})
    db.insert_rows(table, **{c: np.asarray(v, data[c].dtype)
                             for c, v in rows.items()})


def _delete(db, table, rng, n):
    live = np.flatnonzero(np.asarray(db.tables[table].valid))
    mask = np.zeros(db.tables[table].capacity, dtype=bool)
    mask[rng.choice(live, n, replace=False)] = True
    db.delete_rows(table, mask)


def _beyond(db, model, tables, dim):
    """One ``dim`` row keyed 3000 past its range, and a row of each churned
    table that joins it."""
    (col, key), = {(c, k) for t in tables
                   for c, (d, k) in _references(model, t).items()
                   if d == dim}
    row = {c: v[:1].copy() for c, v in db.tables[dim].to_numpy().items()}
    row["rid"][:] = _next_rid(db, dim)
    row[key][:] = db.stats[dim].minmax[key][1] + 3000
    db.insert_rows(dim, **row)
    for t in tables:
        _insert(db, model, t, np.random.default_rng(0), 1,
                fixed={col: row[key]})


def _rounds(kind, model, tables, dim, rng):
    if kind == "beyond":
        return [lambda d: _beyond(d, model, tables, dim)] * 2
    ins = kind in ("inserts", "mixed")
    dels = kind in ("deletes", "mixed")

    def churn(d):
        for t in tables:
            if ins:
                _insert(d, model, t, rng, 12)
            if dels:
                _delete(d, t, rng, 9)
    return [churn]


def _assert_views_fresh(engine, db, churned, result):
    """Materialized views were maintained in place, to the content a fresh
    materialization has."""
    for cv in engine._views.values():
        query = ViewDef(cv.name, cv.pattern).as_query()
        fresh = execute_query(Database(dict(db.tables)), query)
        assert table_digest(cv.table) == table_digest(fresh)
        if churned & {r.table for r in query.relations}:
            assert cv.name in result.refresh.views_maintained


@functools.lru_cache(maxsize=None)
def _pristine(name):
    """Each model's database as generated; cases churn snapshots of it."""
    return MODELS[name][1]()


def check_refresh(name, kind):
    """Churn a snapshot of the model's database by ``kind`` under an
    auto-refresh engine; hold every round to a fresh extract and the
    oracle."""
    model_fn, _, tables, dim = MODELS[name]
    model = model_fn()
    db = _pristine(name).snapshot()
    compiler = PipelineCompiler()
    engine = ExtractionEngine(db, compiler=compiler, auto_refresh=True)
    first = engine.extract(model)
    assert first.refresh.path == "cold"
    rng = np.random.default_rng(1)
    churned = set(tables) | ({dim} if kind == "beyond" else set())
    for mutate in _rounds(kind, model, tables, dim, rng):
        mutate(db)
        r = engine.extract(model)
        assert r.refresh.path == "delta"
        fresh = ExtractionEngine(Database(dict(db.tables))).extract(model)
        for part in ("vertices", "edges"):
            assert ({k: table_digest(t) for k, t in getattr(r, part).items()}
                    == {k: table_digest(t)
                        for k, t in getattr(fresh, part).items()}), part
        assert_edges_match(r.edges, edge_keys(db, model))
        assert_vertices_match(r.vertices, db, model)
        if first.provenance.views_built:
            _assert_views_fresh(engine, db, churned, r)
    # one cached result, brought forward every round
    assert engine.cache_info()["results"] == 1
    if kind == "beyond":
        assert compiler.stats["retries"] > 0
    if first.provenance.views_built:
        # a request that reads the maintained views as free JS-MV rewrites
        r2 = engine.extract(model, method="extgraph-mv", auto_refresh=False)
        assert_edges_match(r2.edges, edge_keys(db, model))
