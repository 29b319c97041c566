"""The numpy oracle of the graph system, read through a graph model.

``bench.reference`` computes each edge label's bag of (src, dst) pairs by a
chain of equijoins in numpy, and the analytics layer's dense vertex
numbering, importing nothing of the program under test.  It reads a graph
in the benchmark configuration's shape; :func:`graph_spec` writes a
:class:`repro.core.model.GraphModel` in that shape through
``repro.api.model_to_spec``, and :func:`tables` hands it a database's
valid rows.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from bench import reference
from repro.api import model_to_spec


def graph_spec(model) -> dict:
    """The model as ``bench.reference`` reads it: relations as
    ``[alias, table]`` pairs, vertices keyed by ``id``."""
    spec = model_to_spec(model)
    edges = []
    for e in spec["edges"]:
        assert all("filters" not in r for r in e["relations"]), (
            "the oracle's equijoins apply no scan filters")
        edges.append({**e, "relations": [[r["alias"], r["table"]]
                                         for r in e["relations"]]})
    vertices = [{"label": v["label"], "table": v["table"], "id": v["id_col"]}
                for v in spec["vertices"]]
    return {"name": spec["name"], "vertices": vertices, "edges": edges}


def tables(db) -> Dict[str, Dict[str, np.ndarray]]:
    """Every table's valid rows, as host arrays."""
    return {name: t.to_numpy() for name, t in db.tables.items()}


def edge_keys(db, model) -> Dict[str, np.ndarray]:
    """Each edge label's sorted (src, dst) pair keys, by the oracle."""
    bags = reference.edge_bags(tables(db), graph_spec(model))
    return {label: np.sort(reference.pair_keys(s, d))
            for label, (s, d) in bags.items()}


def assert_edges_match(edges, want: Dict[str, np.ndarray]) -> None:
    """The program's edge tables hold exactly the oracle's bags."""
    assert sorted(edges) == sorted(want)
    for label, keys in want.items():
        data = edges[label].to_numpy()
        have = np.sort(reference.pair_keys(data["src"], data["dst"]))
        assert np.array_equal(have, keys), (
            f"edge {label!r}: {len(have)} rows vs the oracle's {len(keys)}, "
            f"{reference.bag_mismatch(have, keys)} differ")


def assert_vertices_match(vertices, db, model) -> None:
    """Each vertex label holds exactly the oracle's ascending ids."""
    numbering = reference.dense_numbering(tables(db), graph_spec(model))
    assert sorted(vertices) == sorted(numbering)
    for label, (_, ids) in numbering.items():
        have = np.sort(vertices[label].to_numpy()["id"].astype(np.int64))
        assert np.array_equal(have, ids), f"vertex {label!r}"
