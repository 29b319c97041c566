"""Graph analytics subsystem: kernel/reference parity, algorithms vs the
numpy ground truth, the engine's extract->analyze loop with its
content-addressed CSR cache, and each dataset's served graph against numpy
over the oracle's edges.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.graph import reference as gref
from repro.graph.algorithms import degree_stats, khop, pagerank, wcc
from repro.graph.csr import CSRGraph, _coo_to_csr
from repro.kernels import ref as kref
from repro.kernels.frontier import frontier_expand
from repro.kernels.label_prop import edge_min_label
from repro.kernels.spmv import edge_spmv


# ---------------------------------------------------------------------------
# CSR-shaped COO fixtures: ragged degrees, empty rows, single vertex
# ---------------------------------------------------------------------------

def _coo_case(name):
    rng = np.random.default_rng(hash(name) % 2**31)
    if name == "single_vertex":
        # one vertex, a self-loop, plus an invalid padding slot
        return (np.array([0, 0], np.int32), np.array([0, 0], np.int32),
                np.array([True, False]), 1)
    if name == "empty_rows":
        # 64 vertices but every edge confined to the first 4 — long empty tail
        n_e = 37
        return (rng.integers(0, 4, n_e).astype(np.int32),
                rng.integers(0, 4, n_e).astype(np.int32),
                rng.random(n_e) < 0.7, 64)
    if name == "ragged":
        # zipf-skewed degrees across a tile boundary (n_v > SEG_BLOCK forces
        # multiple segment tiles in the kernels' grids)
        n_e, n_v = 4000, 1500
        src = np.minimum(rng.zipf(1.3, n_e) - 1, n_v - 1).astype(np.int32)
        return (src, rng.integers(0, n_v, n_e).astype(np.int32),
                rng.random(n_e) < 0.8, n_v)
    if name == "all_invalid":
        n_e = 16
        return (rng.integers(0, 8, n_e).astype(np.int32),
                rng.integers(0, 8, n_e).astype(np.int32),
                np.zeros(n_e, bool), 8)
    raise KeyError(name)


CASES = ["single_vertex", "empty_rows", "ragged", "all_invalid"]


@pytest.mark.parametrize("case", CASES)
def test_edge_spmv_matches_ref(case):
    src, dst, valid, n = _coo_case(case)
    rng = np.random.default_rng(1)
    x = rng.normal(size=n).astype(np.float32)
    got = edge_spmv(jnp.asarray(src), jnp.asarray(dst), jnp.asarray(valid),
                    jnp.asarray(x), n, interpret=True)
    want = kref.edge_spmv(jnp.asarray(src), jnp.asarray(dst),
                          jnp.asarray(valid), jnp.asarray(x), n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("case", CASES)
def test_edge_min_label_matches_ref(case):
    src, dst, valid, n = _coo_case(case)
    rng = np.random.default_rng(2)
    labels = rng.permutation(n).astype(np.int32)
    got = edge_min_label(jnp.asarray(src), jnp.asarray(dst),
                         jnp.asarray(valid), jnp.asarray(labels), n,
                         interpret=True)
    want = kref.edge_min_label(jnp.asarray(src), jnp.asarray(dst),
                               jnp.asarray(valid), jnp.asarray(labels), n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("case", CASES)
def test_frontier_expand_matches_ref(case):
    src, dst, valid, n = _coo_case(case)
    rng = np.random.default_rng(3)
    frontier = rng.random(n) < 0.3
    visited = (rng.random(n) < 0.2) | frontier
    got = frontier_expand(jnp.asarray(src), jnp.asarray(dst),
                          jnp.asarray(valid), jnp.asarray(frontier),
                          jnp.asarray(visited), n, interpret=True)
    want = kref.frontier_expand(jnp.asarray(src), jnp.asarray(dst),
                                jnp.asarray(valid), jnp.asarray(frontier),
                                jnp.asarray(visited), n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Algorithms over a CSRGraph vs the numpy ground truth
# ---------------------------------------------------------------------------

def _mk_csr(src, dst, n, label="E"):
    src, dst = jnp.asarray(src), jnp.asarray(dst)
    valid = jnp.ones((src.shape[0],), bool)
    off, tgt, srt = _coo_to_csr(src, dst, valid, n)
    return CSRGraph(
        num_vertices=n,
        vertex_ranges={"V": (0, n)},
        vertex_ids=jnp.arange(n, dtype=jnp.int32),
        offsets={label: off},
        targets={label: tgt},
        sources={label: srt},
        edge_counts={label: int(src.shape[0])},
    )


@pytest.fixture(scope="module")
def random_csr():
    rng = np.random.default_rng(7)
    n_v, n_e = 300, 1200
    src = np.minimum(rng.zipf(1.4, n_e) - 1, n_v - 1).astype(np.int32)
    dst = rng.integers(0, n_v, n_e).astype(np.int32)
    return _mk_csr(src, dst, n_v), src, dst, n_v


@pytest.mark.parametrize("use_kernel", [False, True])
def test_pagerank_matches_numpy(random_csr, use_kernel):
    csr, src, dst, n = random_csr
    got = np.asarray(pagerank(csr, iters=12, use_kernel=use_kernel))
    want = gref.pagerank_np(src, dst, np.ones(len(src), bool), n, iters=12)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert abs(got.sum() - 1.0) < 1e-3  # dangling mass redistributed


@pytest.mark.parametrize("use_kernel", [False, True])
def test_wcc_matches_numpy(random_csr, use_kernel):
    csr, src, dst, n = random_csr
    got = np.asarray(wcc(csr, use_kernel=use_kernel))
    want = gref.wcc_np(src, dst, np.ones(len(src), bool), n)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("k", [0, 1, 3])
def test_khop_matches_numpy(random_csr, use_kernel, k):
    csr, src, dst, n = random_csr
    seeds = np.zeros(n, bool)
    seeds[[0, 5]] = True
    got = np.asarray(khop(csr, jnp.asarray(seeds), k=k,
                          use_kernel=use_kernel))
    want = gref.khop_np(src, dst, np.ones(len(src), bool), seeds, n, k=k)
    np.testing.assert_array_equal(got, want)
    # index-array seed spelling agrees with the mask spelling
    got_idx = np.asarray(khop(csr, jnp.asarray([0, 5]), k=k,
                              use_kernel=use_kernel))
    np.testing.assert_array_equal(got_idx, want)


def test_degree_stats_matches_numpy(random_csr):
    csr, src, dst, n = random_csr
    got = degree_stats(csr, use_kernel=False)
    want = gref.degree_stats_np(src, dst, np.ones(len(src), bool), n)
    np.testing.assert_array_equal(np.asarray(got["out_degree"]),
                                  want["out_degree"])
    np.testing.assert_array_equal(np.asarray(got["in_degree"]),
                                  want["in_degree"])
    assert int(got["num_edges"]) == want["num_edges"]
    assert int(got["isolated"]) == want["isolated"]


def test_csr_transpose_and_degrees(random_csr):
    csr, src, dst, n = random_csr
    t = csr.transpose()
    ts, td, tv = [np.asarray(a) for a in t.coo("E")]
    assert (set(zip(ts[tv].tolist(), td[tv].tolist()))
            == set(zip(dst.tolist(), src.tolist())))
    np.testing.assert_array_equal(np.asarray(t.out_degree("E")),
                                  np.asarray(csr.in_degree("E")))
    # symmetric COO doubles the edges
    _, _, sv = csr.coo("E", symmetric=True)
    assert int(np.asarray(sv).sum()) == 2 * len(src)


def test_csr_coo_rejects_unknown_label(random_csr):
    csr = random_csr[0]
    with pytest.raises(KeyError):
        csr.coo("nope")


# ---------------------------------------------------------------------------
# Engine integration: extract -> analyze with the content-addressed CSR cache
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    from repro.api import ExtractionEngine
    from repro.data import make_tpcds
    return ExtractionEngine(make_tpcds(sf=1, seed=0))


def test_engine_analyze_pagerank_matches_numpy(engine):
    from repro.data import fraud_model
    model = fraud_model("store")

    cold = engine.analyze(model, algorithm="pagerank", label="Buy", iters=15)
    assert not cold.provenance.csr_cache_hit

    warm = engine.analyze(model, algorithm="pagerank", label="Buy", iters=15)
    assert warm.provenance.csr_cache_hit          # CSR NOT rebuilt
    assert warm.provenance.extraction.plan_cache_hit
    assert warm.provenance.csr_key == cold.provenance.csr_key
    assert warm.timings.csr_build_s < cold.timings.csr_build_s
    assert engine.cache_info()["csrs"] == 1

    src, dst, valid = [np.asarray(a) for a in cold.csr.coo("Buy")]
    want = gref.pagerank_np(src, dst, valid, cold.csr.num_vertices, iters=15)
    np.testing.assert_allclose(np.asarray(warm.values), want, atol=1e-5)


def test_engine_graph_view_shares_cache(engine):
    from repro.data import fraud_model
    result = engine.extract(fraud_model("store"))
    before = engine.cache_info()["csrs"]
    csr = result.graph_view()
    assert result.graph_view() is csr             # memoized on the result
    assert engine.cache_info()["csrs"] == max(before, 1)
    ds = engine.analyze(fraud_model("store"), algorithm="degree_stats")
    assert ds.provenance.csr_cache_hit            # same content address
    assert ds.csr is csr


def test_engine_analyze_other_algorithms(engine):
    from repro.data import fraud_model
    model = fraud_model("store")
    w = engine.analyze(model, algorithm="wcc")
    assert w.provenance.csr_cache_hit
    labels = np.asarray(w.values)
    assert labels.shape == (w.csr.num_vertices,)
    k = engine.analyze(model, algorithm="khop", seeds=np.arange(2), k=2,
                       label="Buy")
    d = np.asarray(k.values)
    assert d.min() >= -1 and (d == 0).sum() == 2


def test_engine_analyze_rejects_unknown_algorithm(engine):
    from repro.data import fraud_model
    with pytest.raises(ValueError, match="unknown algorithm"):
        engine.analyze(fraud_model("store"), algorithm="sssp")


def test_csr_cache_is_content_addressed_across_methods(engine):
    """ringo produces the same graph as extgraph -> same content address."""
    from repro.data import fraud_model
    model = fraud_model("store")
    engine.analyze(model, algorithm="degree_stats")          # ensure cached
    via_ringo = engine.analyze(model, algorithm="degree_stats",
                               method="ringo")
    assert via_ringo.provenance.csr_cache_hit


def test_standalone_result_graph_view(engine):
    """Results detached from an engine still get a (locally memoized) CSR."""
    import dataclasses
    from repro.data import fraud_model

    res = engine.extract(fraud_model("store"))
    detached = dataclasses.replace(res, _engine=None, _csr=None)
    csr = detached.graph_view()
    assert csr is detached.graph_view()            # memoized locally
    assert csr.num_vertices == sum(
        int(t.num_rows()) for t in res.vertices.values())


# ---------------------------------------------------------------------------
# Served analytics on each dataset's extracted graph, against numpy over
# the oracle's edges: bench.reference's equijoins and dense numbering
# (vertex labels in name order, ids ascending inside each)
# ---------------------------------------------------------------------------

def _datasets():
    from repro.data import (dblp_model, fraud_model, imdb_model, make_dblp,
                            make_imdb, make_tpcds)
    return {"fraud": (lambda: fraud_model("store"),
                      lambda: make_tpcds(sf=1, seed=0)),
            "dblp": (dblp_model, lambda: make_dblp(scale=1, seed=1)),
            "imdb": (imdb_model, lambda: make_imdb(scale=1, seed=2))}


@pytest.fixture(scope="module")
def served():
    """name -> (engine, model, oracle src, oracle dst, vertex count)."""
    from bench import reference
    from oracle import graph_spec, tables
    from repro.api import ExtractionEngine

    memo = {}

    def get(name):
        if name not in memo:
            model_fn, db_fn = _datasets()[name]
            model, db = model_fn(), db_fn()
            graph, data = graph_spec(model), tables(db)
            numbering = reference.dense_numbering(data, graph)
            src, dst = reference.dense_edges(
                graph, reference.edge_bags(data, graph), numbering)
            n = sum(len(ids) for _, ids in numbering.values())
            memo[name] = (ExtractionEngine(db), model, src, dst, n)
        return memo[name]
    return get


def _seeds(n):
    return np.arange(0, n, max(1, n // 8))[:8]


@pytest.mark.parametrize("algorithm", ["pagerank", "wcc", "khop",
                                       "degree_stats"])
@pytest.mark.parametrize("name", ["fraud", "dblp", "imdb"])
def test_served_analytics_match_numpy_over_the_oracle(served, name,
                                                      algorithm):
    from bench import reference

    engine, model, src, dst, n = served(name)
    params = {"pagerank": {"iters": 15}, "khop": {"seeds": _seeds(n), "k": 2}}
    res = engine.analyze(model, algorithm=algorithm,
                         **params.get(algorithm, {}))
    assert res.csr.num_vertices == n
    valid = np.ones(len(src), bool)
    if algorithm == "pagerank":
        np.testing.assert_allclose(
            np.asarray(res.values),
            gref.pagerank_np(src, dst, valid, n, iters=15),
            rtol=1e-4, atol=1e-8)
    elif algorithm == "wcc":
        np.testing.assert_array_equal(np.asarray(res.values),
                                      gref.wcc_np(src, dst, valid, n))
    elif algorithm == "khop":
        seeds = np.zeros(n, bool)
        seeds[_seeds(n)] = True
        got = np.asarray(res.values)
        np.testing.assert_array_equal(
            got, gref.khop_np(src, dst, valid, seeds, n, k=2))
        np.testing.assert_array_equal(
            got, reference.khop(src, dst, _seeds(n), n, 2))
    else:
        want = gref.degree_stats_np(src, dst, valid, n)
        for key in ("out_degree", "in_degree"):
            np.testing.assert_array_equal(np.asarray(res.values[key]),
                                          want[key])
        for key in ("num_edges", "max_out_degree", "max_in_degree",
                    "isolated"):
            assert int(res.values[key]) == want[key], key
