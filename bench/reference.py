"""Plain reference of the graph system's answers, in numpy.

It imports nothing of the program under test and takes nothing it made:
it reads the tables the benchmark generated and the configuration's graph
model, and computes

* each edge label's bag of (src, dst) pairs by a chain of equijoins
  (:func:`edge_bags`);
* the dense vertex numbering of the analytics layer: vertex labels in name
  order, each owning a contiguous range, ids ascending inside it
  (:func:`dense_numbering`), and directed k-hop distances over it
  (:func:`khop`), and the digest the service gives such an answer
  (:func:`array_digest`).

``id_dtype`` is the precision the answers carry vertex ids (and dense
indices) in.  The configuration states int32; the control computes the
same answers through ``numpy.int16``, the nearest lower precision, and
has to come out wrong.  Keys always compare exactly.
"""
from __future__ import annotations

import hashlib
from typing import Dict, List, Tuple

import numpy as np

Tables = Dict[str, Dict[str, np.ndarray]]


def _ref(text: str) -> Tuple[str, str]:
    alias, col = text.strip().split(".")
    return alias, col


def _cond(text: str) -> Tuple[Tuple[str, str], Tuple[str, str]]:
    left, right = text.split("==")
    return _ref(left), _ref(right)


def chain_join(tables: Tables, edge: dict, id_dtype=np.int32
               ) -> Tuple[np.ndarray, np.ndarray]:
    """(src, dst) of every row of the edge's join, as int64 arrays.

    Relations join in the listed order; each one after the first joins on
    the first condition that links it to a relation already joined, and
    any further such condition filters.  The ids come out through
    ``id_dtype``.
    """
    rel = {alias: table for alias, table in edge["relations"]}
    conds = [_cond(c) for c in edge["joins"]]
    src, dst = _ref(edge["src_col"]), _ref(edge["dst_col"])

    def col(alias: str, name: str, rows: np.ndarray) -> np.ndarray:
        return tables[rel[alias]][name][rows]

    def key(values: np.ndarray) -> np.ndarray:
        return values.astype(np.int64)

    order = [alias for alias, _ in edge["relations"]]
    first = order[0]
    frame = {first: np.arange(len(next(iter(tables[rel[first]].values()))))}
    pending = list(conds)
    for alias in order[1:]:
        links = [c for c in pending
                 if (c[0][0] == alias and c[1][0] in frame)
                 or (c[1][0] == alias and c[0][0] in frame)]
        if not links:
            raise ValueError(f"relation {alias} joins nothing before it")
        (a, ca), (b, cb) = links[0]
        if a == alias:
            (a, ca), (b, cb) = (b, cb), (a, ca)
        left = key(col(a, ca, frame[a]))
        right = key(tables[rel[alias]][cb])
        order_r = np.argsort(right, kind="stable")
        sorted_r = right[order_r]
        lo = np.searchsorted(sorted_r, left, "left")
        hi = np.searchsorted(sorted_r, left, "right")
        counts = hi - lo
        take = np.repeat(np.arange(len(left)), counts)
        starts = np.repeat(lo - np.cumsum(counts) + counts, counts)
        match = order_r[np.arange(len(take)) + starts]
        frame = {k: v[take] for k, v in frame.items()}
        frame[alias] = match
        for extra in links[1:]:
            (x, cx), (y, cy) = extra
            keep = key(col(x, cx, frame[x])) == key(col(y, cy, frame[y]))
            frame = {k: v[keep] for k, v in frame.items()}
        pending = [c for c in pending if c not in links]
        needed = {a for c in pending for a in (c[0][0], c[1][0])}
        needed |= {src[0], dst[0]}
        frame = {k: v for k, v in frame.items() if k in needed}
    s = col(src[0], src[1], frame[src[0]])
    d = col(dst[0], dst[1], frame[dst[0]])
    return (s.astype(id_dtype).astype(np.int64),
            d.astype(id_dtype).astype(np.int64))


def edge_bags(tables: Tables, graph: dict, id_dtype=np.int32
              ) -> Dict[str, Tuple[np.ndarray, np.ndarray]]:
    return {e["label"]: chain_join(tables, e, id_dtype)
            for e in graph["edges"]}


def pair_keys(src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """One sortable int64 per (src, dst) pair (ids are 32-bit)."""
    return (np.asarray(src, np.int64) << 32) | (np.asarray(dst, np.int64)
                                               & 0xFFFFFFFF)


def bag_mismatch(a: np.ndarray, b: np.ndarray) -> int:
    """Size of the multiset symmetric difference of two key arrays."""
    ua, ca = np.unique(a, return_counts=True)
    ub, cb = np.unique(b, return_counts=True)
    _, ia, ib = np.intersect1d(ua, ub, assume_unique=True,
                               return_indices=True)
    common = int(np.minimum(ca[ia], cb[ib]).sum())
    return int(len(a) + len(b) - 2 * common)


def dense_numbering(tables: Tables, graph: dict
                    ) -> Dict[str, Tuple[int, np.ndarray]]:
    """label -> (first dense index, ascending vertex ids of the label)."""
    out: Dict[str, Tuple[int, np.ndarray]] = {}
    base = 0
    for v in sorted(graph["vertices"], key=lambda v: v["label"]):
        ids = np.sort(tables[v["table"]][v["id"]].astype(np.int64))
        out[v["label"]] = (base, ids)
        base += len(ids)
    return out


def dense_edges(graph: dict, bags, numbering, id_dtype=np.int32
                ) -> Tuple[np.ndarray, np.ndarray]:
    """All labels' edges over dense indices, labels in name order; the
    indices come out through ``id_dtype``."""
    labels = {e["label"]: e for e in graph["edges"]}
    srcs: List[np.ndarray] = []
    dsts: List[np.ndarray] = []
    for label in sorted(labels):
        e = labels[label]
        s, d = bags[label]
        for ids, lab, out in ((s, e["src"], srcs), (d, e["dst"], dsts)):
            base, sorted_ids = numbering[lab]
            idx = base + np.searchsorted(sorted_ids, ids)
            out.append(idx.astype(id_dtype).astype(np.int64))
    return np.concatenate(srcs), np.concatenate(dsts)


def khop(src: np.ndarray, dst: np.ndarray, seeds: np.ndarray,
         num_vertices: int, k: int) -> np.ndarray:
    """Directed BFS distance from the seed indices, -1 beyond k hops."""
    dist = np.full(num_vertices, -1, np.int32)
    dist[seeds] = 0
    frontier = dist == 0
    visited = frontier.copy()
    for hop in range(1, k + 1):
        hit = np.zeros(num_vertices, bool)
        hit[dst[frontier[src]]] = True
        nxt = hit & ~visited
        dist[nxt] = hop
        visited |= nxt
        frontier = nxt
    return dist


def array_digest(values: np.ndarray) -> str:
    return hashlib.sha1(np.ascontiguousarray(values).tobytes()
                        ).hexdigest()[:16]
