"""Extraction against the numpy oracle: the cases of ``test_oracle*.py``.

Every other extraction test compares the program with itself (Ringo runs
the same join core as the planned methods).  Here each planned method, on
the compiled pipeline and on the eager path, is held to
``bench.reference``'s chain of numpy equijoins: every edge label's
(src, dst) bag must match exactly, and every vertex label's ids.
Databases are at the sizes ``test_extraction.py`` uses.
"""
import functools

from oracle import assert_edges_match, assert_vertices_match, edge_keys
from repro.api import ExtractionEngine
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

METHODS = ("extgraph", "extgraph-oj", "extgraph-mv")
DATABASES = {"tpcds": lambda: make_tpcds(sf=2, seed=0),
             "dblp": lambda: make_dblp(scale=1, seed=1),
             "imdb": lambda: make_imdb(scale=1, seed=2)}
MODELS = {
    "fraud_store": (lambda: fraud_model("store"), "tpcds"),
    "fraud_catalog": (lambda: fraud_model("catalog"), "tpcds"),
    "fraud_web": (lambda: fraud_model("web"), "tpcds"),
    "recommendation_store": (lambda: recommendation_model("store"), "tpcds"),
    "recommendation_catalog": (lambda: recommendation_model("catalog"),
                               "tpcds"),
    "recommendation_web": (lambda: recommendation_model("web"), "tpcds"),
    "combined": (combined_model, "tpcds"),
    "dblp": (dblp_model, "dblp"),
    "imdb": (imdb_model, "imdb"),
}


@functools.lru_cache(maxsize=None)
def _database(name):
    return DATABASES[name]()


@functools.lru_cache(maxsize=None)
def _oracle_keys(name):
    model_fn, db_name = MODELS[name]
    return edge_keys(_database(db_name), model_fn())


def check_extraction(name, method, compiled):
    """One planned method's extract of the model matches the oracle."""
    model_fn, db_name = MODELS[name]
    db = _database(db_name)
    model = model_fn()
    got = ExtractionEngine(db, compiled=compiled).extract(model,
                                                          method=method)
    assert_edges_match(got.edges, _oracle_keys(name))
    assert_vertices_match(got.vertices, db, model)
