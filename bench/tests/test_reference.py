"""The plain reference against the program's own oracles, at a tiny size
on the CPU: the eager (uncompiled) extraction path, the numpy k-hop of
``repro.graph.reference``, and the tables' shape."""
import numpy as np
import pytest

from bench import data, reference, spec
from bench.harness import _database, _graph_model

SEED = 2**31 + 11


def _config(name):
    bench = spec.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["config"] == name)
    return spec.resolve(bench, cell["name"])["config"]


@pytest.fixture(scope="module")
def eager():
    from repro.api import ExtractionEngine

    config = _config("tpcds_sf1_fraud")
    tables = data.make_tables(config, SEED, shrink=2000)
    engine = ExtractionEngine(_database(tables), compiled=False)
    graph = engine.extract(_graph_model(config["graph"])).graph
    return config, tables, graph


def test_tables_follow_the_seed():
    config = _config("tpcds_sf1_fraud")
    a = data.make_tables(config, SEED, shrink=2000)
    b = data.make_tables(config, SEED, shrink=2000)
    c = data.make_tables(config, SEED + 1, shrink=2000)
    assert all(np.array_equal(a[t][k], b[t][k]) for t in a for k in a[t])
    assert not np.array_equal(a["store_sales"]["ss_customer_sk"],
                              c["store_sales"]["ss_customer_sk"])
    assert len(a["store_sales"]["ss_ticket_number"]) == 2880404 // 2000
    assert len(a["store_sales"]) == 23


def test_fact_rows_come_in_tickets():
    """8-16 lines a ticket, one customer and store a ticket, distinct
    items within a ticket, as dsdgen draws store_sales."""
    config = _config("tpcds_sf1_fraud")
    ss = data.make_tables(config, SEED, shrink=100)["store_sales"]
    null = config["null_key"]
    ticket = ss["ss_ticket_number"]
    assert np.all(np.diff(ticket) >= 0) and ticket[0] == 1
    sizes = np.bincount(ticket)[1:]
    assert sizes[:-1].min() >= 8 and sizes.max() <= 16
    assert 11 < sizes[:-1].mean() < 13
    for col in ("ss_customer_sk", "ss_store_sk"):
        v = ss[col]
        same = (np.diff(ticket) != 0) | (v[1:] == v[:-1]) \
            | (v[1:] == null) | (v[:-1] == null)
        assert same.all(), col
    pairs = ticket.astype(np.int64) * 2**32 + ss["ss_item_sk"]
    assert len(np.unique(pairs)) == len(pairs)


@pytest.mark.parametrize("col,share", [
    ("ss_customer_sk", 0.045), ("ss_store_sk", 0.045),
    ("ss_item_sk", 0.0), ("ss_ticket_number", 0.0)])
def test_null_keys_follow_the_configuration(col, share):
    config = _config("tpcds_sf1_fraud")
    ss = data.make_tables(config, SEED, shrink=10)["store_sales"]
    got = float(np.mean(ss[col] == config["null_key"]))
    assert got == pytest.approx(share, abs=0.005)


def test_keys_are_uniform_over_the_dimension():
    """No key takes a pile of rows: the most frequent customer and item
    hold under 0.1% of them (a Zipf(1.2) head held 18%)."""
    config = _config("tpcds_sf1_fraud")
    ss = data.make_tables(config, SEED)["store_sales"]
    for col, n in (("ss_customer_sk", 100000), ("ss_item_sk", 18000)):
        v = ss[col][ss[col] != config["null_key"]]
        counts = np.bincount(v, minlength=n + 1)[1:]
        assert (counts > 0).mean() > 0.9
        assert counts.max() < 1e-3 * len(v)


def test_null_keys_join_nothing(eager):
    config, tables, graph = eager
    null = config["null_key"]
    ss = tables["store_sales"]
    buy = graph.edges["Buy"]
    assert int(np.asarray(buy.valid).sum()) == int(
        (ss["ss_customer_sk"] != null).sum())


def test_edge_bags_equal_the_eager_engine(eager):
    config, tables, graph = eager
    bags = reference.edge_bags(tables, config["graph"])
    assert set(bags) == set(graph.edges)
    for label, (s, d) in bags.items():
        t = graph.edges[label]
        valid = np.asarray(t.valid)
        got = reference.pair_keys(np.asarray(t["src"])[valid],
                                  np.asarray(t["dst"])[valid])
        assert len(s) > 0
        assert reference.bag_mismatch(got, reference.pair_keys(s, d)) == 0


def test_khop_equals_graph_reference(eager):
    from repro.graph import build_csr
    from repro.graph.reference import khop_np

    config, tables, graph = eager
    numbering = reference.dense_numbering(tables, config["graph"])
    n = sum(len(ids) for _, ids in numbering.values())
    src, dst = reference.dense_edges(
        config["graph"], reference.edge_bags(tables, config["graph"]),
        numbering)
    csr = build_csr(graph, _graph_model(config["graph"]))
    csrc, cdst, cvalid = (np.asarray(a) for a in csr.coo(None))
    assert reference.bag_mismatch(
        reference.pair_keys(csrc[cvalid], cdst[cvalid]),
        reference.pair_keys(src, dst)) == 0
    seeds = np.arange(0, n, 7)
    mask = np.zeros(n, bool)
    mask[seeds] = True
    for k in (1, 2, 3):
        assert np.array_equal(reference.khop(src, dst, seeds, n, k),
                              khop_np(src, dst, np.ones(len(src), bool),
                                      mask, n, k))


def test_bag_mismatch_counts_the_symmetric_difference():
    a = np.array([1, 1, 2, 3])
    b = np.array([1, 2, 2, 4])
    assert reference.bag_mismatch(a, b) == 4     # {1, 3} vs {2, 4}
    assert reference.bag_mismatch(a, a) == 0
