"""The range-probe choice of a compiled unit, step by step.

A join step probes through a table of prefix counts over its build key's
range where the stats bound that range to at most ``RANGE_SPAN_FACTOR``
times the step's arrays, and bisects otherwise.  One JS-OJ unit over a
star schema (a fact table whose foreign keys are NULL in one row of 16,
three dimensions keyed 0..63) runs with one
dimension's key laid out per case, as the build side of either the inner
step of the shared chain (``item``) or an outer-branch attachment
(``customer``).  Each case checks the step's planned probe, the
``pipeline_probe_rows_total{method}`` split of the kept attempts, and that
the bag equals the eager path's.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.database import Database
from repro.core.executor import ensure_view, execute_merged
from repro.core.jsoj import merge_queries
from repro.core.model import ColumnRef, JoinCond, JoinQuery, Predicate, Relation
from repro.core.pipeline import (
    RANGE_SPAN_FACTOR,
    PipelineCompiler,
    _step_sides,
    probe_capacities,
)
from repro.core.shared import find_embeddings, subgraph_pattern
from repro.relational import NULL_KEY, Table
from repro.relational.ops import table_digest

FACT_ROWS = 512
DIM_ROWS = 64
# the span limit of every step here: the probe side (the fact table, or a
# chain bound by it) outgrows each dimension
LIMIT = RANGE_SPAN_FACTOR * FACT_ROWS
ROLES = {"inner": ("item", "i_id"), "outer": ("customer", "c_id")}


def _spread(span):
    """``DIM_ROWS`` unique keys from 0 to ``span``."""
    keys = np.unique(np.linspace(0, span, DIM_ROWS).round().astype(np.int64))
    assert len(keys) == DIM_ROWS
    return keys


# layout -> (build keys, the probe the step takes)
LAYOUTS = {
    "dense": (np.arange(DIM_ROWS), "range"),
    "negative_min": (np.arange(DIM_ROWS) - 40, "range"),
    "non_unique": (np.repeat(np.arange(DIM_ROWS // 2), 2), "range"),
    # max - min of LIMIT - 1 buckets to a span of LIMIT; of LIMIT, to 2x
    "span_at_limit": (_spread(LIMIT - 1), "range"),
    "span_over_limit": (_spread(LIMIT), "search"),
    "no_minmax": (np.arange(DIM_ROWS), "search"),
    "view": (np.arange(DIM_ROWS), "range"),
    "stale": (np.arange(DIM_ROWS), "search"),
}


def _dim(col, keys):
    return Table.from_arrays(rid=np.arange(len(keys), dtype=np.int32),
                             **{col: np.asarray(keys, np.int32)})


def _star(role, layout):
    """``(db, {table: (name read, key column)})``: the star schema with
    the role's dimension keyed by the layout."""
    rng = np.random.default_rng(0)
    table, col = ROLES[role]
    keys = {"item": np.arange(DIM_ROWS), "customer": np.arange(DIM_ROWS),
            "outlet": np.arange(DIM_ROWS)}
    keys[table] = LAYOUTS[layout][0]
    db = Database()
    for name, key in (("item", "i_id"), ("customer", "c_id"),
                      ("outlet", "o_id")):
        db.add_table(name, _dim(key, keys[name]))

    def fks(name):
        drawn = rng.choice(np.unique(keys[name]), FACT_ROWS)
        return np.where(rng.random(FACT_ROWS) < 1 / 16, NULL_KEY,
                        drawn).astype(np.int32)

    db.add_table("fact", Table.from_arrays(
        rid=np.arange(FACT_ROWS, dtype=np.int32), i_sk=fks("item"),
        c_sk=fks("customer"), o_sk=fks("outlet")))
    reads = {"item": ("item", "i_id"), "customer": ("customer", "c_id"),
             "outlet": ("outlet", "o_id")}
    if layout == "no_minmax":
        db.stats[table] = dataclasses.replace(db.stats[table], minmax={},
                                              estimated=True)
    if layout == "view":
        # a view carries its base column's range in estimated stats
        view = f"v_{table}"
        ensure_view(db, view, JoinQuery(
            view, (Relation("X", table, (Predicate("rid", ">=", 0),)),), (),
            ColumnRef("X", "rid"), ColumnRef("X", "rid")))
        assert db.stats[view].estimated
        assert db.stats[view].minmax[f"X.{col}"] == db.stats[table].minmax[col]
        reads[table] = (view, f"X.{col}")
    return db, reads


def _merged(reads):
    """Sell (outlet-fact-item) and Buy (customer-fact-item), merged on
    their shared fact-item join: the inner step builds ``item``, the outer
    branches attach ``outlet`` and ``customer``."""
    item, item_col = reads["item"]

    def query(name, alias, dim, fk):
        table, col = reads[dim]
        return JoinQuery(
            name, (Relation(alias, table), Relation("F", "fact"),
                   Relation("I", item)),
            (JoinCond(alias, col, "F", fk), JoinCond("F", "i_sk", "I",
                                                     item_col)),
            ColumnRef(alias, col), ColumnRef("I", item_col))

    members = [query("Sell", "O", "outlet", "o_sk"),
               query("Buy", "C", "customer", "c_sk")]
    pattern = subgraph_pattern(members[0], (1,))
    return merge_queries(pattern, [(q, find_embeddings(pattern, q)[0])
                                   for q in members])


def _keyed_rows(db):
    """Probe rows with a key of each step: the fact rows into ``item``,
    then the shared chain's rows (each fact row times its item matches)
    into each attachment."""
    keys, counts = np.unique(db.tables["item"].to_numpy()["i_id"],
                             return_counts=True)
    fact = db.tables["fact"].to_numpy()
    keyed = {fk: fact[fk] != NULL_KEY for fk in ("i_sk", "o_sk", "c_sk")}
    chain = np.where(keyed["i_sk"], counts[np.searchsorted(
        keys, fact["i_sk"]).clip(0, len(keys) - 1)], 0)
    return [int(keyed["i_sk"].sum()), int((chain * keyed["o_sk"]).sum()),
            int((chain * keyed["c_sk"]).sum())]


def _probe_rows():
    from repro import obs

    return {m: obs.REGISTRY.value("pipeline_probe_rows_total", method=m)
            for m in ("range", "search")}


@pytest.mark.parametrize("role", list(ROLES))
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_range_probe_choice(monkeypatch, layout, role):
    from repro import obs
    from repro.obs.metrics import MetricsRegistry

    monkeypatch.setattr(obs, "REGISTRY", MetricsRegistry())
    db, reads = _star(role, layout)
    merged = _merged(reads)
    comp = PipelineCompiler()
    got = comp.run_merged(db, merged)
    (prog,) = comp._programs.values()
    sides = _step_sides(prog.kind, prog.unit, prog.orders)
    built = [build for build, _ in sides]
    table = reads[ROLES[role][0]]
    assert built == [reads["item"], reads["outlet"], reads["customer"]]
    step = built.index(table)
    assert probe_capacities(db, prog)[step] == FACT_ROWS
    keyed = _keyed_rows(db)
    want = LAYOUTS[layout][1]
    if layout == "stale":
        # planned over the rows as they were; a key inserted beyond the
        # range afterwards finds the memoized program stale on the device
        assert None not in prog.ranges
        assert _probe_rows() == {"range": sum(keyed), "search": 0}
        kmin, span = prog.ranges[step]
        row = {c: v[:1].copy() for c, v in db.tables[table[0]]
               .to_numpy().items()}
        row[table[1]][:] = kmin + span
        db.insert_rows(table[0], **row)
        before = _probe_rows()
        got = comp.run_merged(db, merged)
        assert comp.stats["retries"] == 1
        kept = comp._unit_memo[("merged", merged)]
        assert kept.ranges[step] is None
        assert kept.ranges[:step] + kept.ranges[step + 1:] == \
            prog.ranges[:step] + prog.ranges[step + 1:]
        rows = _probe_rows()
        assert rows["search"] - before["search"] == keyed[step]
        assert rows["range"] - before["range"] == sum(keyed) - keyed[step]
        comp.run_merged(db, merged)
        assert comp.stats["retries"] == 1
    else:
        assert comp.stats["retries"] == 0
        assert (prog.ranges[step] is None) == (want == "search")
        others = prog.ranges[:step] + prog.ranges[step + 1:]
        assert None not in others
        searched = keyed[step] if want == "search" else 0
        assert _probe_rows() == {"range": sum(keyed) - searched,
                                 "search": searched}
    eager = execute_merged(db, merged)
    assert {k: table_digest(t) for k, t in got.items()} == \
        {k: table_digest(t) for k, t in eager.items()}
