"""Schema-to-graph auto-discovery (repro.discovery).

The quality tests run with FK-name hints *stripped* (every column renamed
``col<j>``): recovery has to come from profiles and compiled containment
checks alone.
Scoring is canonicalized through value-identical column classes — the
synthetic dims carry a surrogate ``rid`` bit-identical to the id column,
and joining on either is the same join.
"""
import json
import sys
import threading
import urllib.request

import numpy as np
import pytest

from repro.api import (
    ExtractionEngine,
    model_from_json,
    model_from_spec,
    model_to_spec,
)
from repro.core.database import Database
from repro.core.pipeline import PipelineCompiler
from repro.discovery import (
    ContainmentChecker,
    anonymize_columns,
    canonicalize_pairs,
    column_equivalence,
    discover,
    edge_recovery,
    fk_pairs,
    infer_join_keys,
    model_fk_pairs,
    precision_recall,
    profile_database,
    profile_table,
    wilson_lower,
)
from repro.discovery.infer import name_similarity
from repro.relational import Table
from repro.relational.table import NULL_KEY


# ---------------------------------------------------------------------------
# stage 1: profiling (KMV sketch)
# ---------------------------------------------------------------------------

def test_kmv_ndv_exact_below_k_and_approx_above():
    rng = np.random.default_rng(0)
    small = rng.integers(0, 100, 20000).astype(np.int32)     # 100 << k=256
    big = rng.integers(0, 3000, 20000).astype(np.int32)      # 3000 >> k
    t = Table.from_arrays(small=small, big=big)
    prof = profile_table("t", t, Database({"t": t}).stats["t"])
    assert prof.columns["small"].ndv == len(np.unique(small))  # exact
    true_big = len(np.unique(big))
    assert abs(prof.columns["big"].ndv - true_big) / true_big < 0.15


def test_profile_key_detection_and_nulls():
    rng = np.random.default_rng(1)
    n = 2048
    t = Table.from_arrays(
        pk=np.arange(n, dtype=np.int32),
        fk=rng.integers(0, 64, n).astype(np.int32),
        sparse=np.where(np.arange(n) % 4 == 0, NULL_KEY,
                        np.arange(n)).astype(np.int32))
    prof = profile_table("t", t, Database({"t": t}).stats["t"])
    assert prof.columns["pk"].key_like()
    assert not prof.columns["fk"].key_like()       # uniqueness ~64/2048
    sp = prof.columns["sparse"]
    assert abs(sp.null_frac - 0.25) < 0.01
    assert not sp.key_like()                       # too many nulls
    assert prof.key_columns() == ("pk",)


def test_profile_database_covers_tables():
    db = Database({"a": Table.from_arrays(x=np.arange(8, dtype=np.int32)),
                   "b": Table.from_arrays(y=np.arange(8, dtype=np.int32))})
    profs = profile_database(db)
    assert set(profs) == {"a", "b"}
    assert profs["a"].rows == 8


# ---------------------------------------------------------------------------
# stage 2: inference
# ---------------------------------------------------------------------------

def test_wilson_lower_rewards_sample_size():
    assert wilson_lower(0, 0) == 0.0
    assert wilson_lower(16, 16) < wilson_lower(512, 512)
    assert wilson_lower(512, 512) > 0.99
    assert wilson_lower(256, 512) == pytest.approx(0.5, abs=0.05)


def test_name_similarity_ignores_generic_tokens():
    # "c_sk" vs "c_id" must match on "c", never on the generic sk/id
    assert name_similarity("c_sk", "c_id", "customer") == 1.0
    assert name_similarity("p_sk", "c_id", "customer") == 0.0
    assert name_similarity("rid", "o_id", "outlet") == 0.0   # all generic


def _fk_toy_db():
    """parent (64 unique keys) <- child.fk; child.noise is a decoy whose
    range escapes the parent's key space."""
    rng = np.random.default_rng(2)
    parent = Table.from_arrays(pid=np.arange(64, dtype=np.int32),
                               payload=rng.integers(0, 5, 64).astype(np.int32))
    child = Table.from_arrays(
        rid=np.arange(512, dtype=np.int32),
        fk=rng.integers(0, 64, 512).astype(np.int32),
        noise=rng.integers(0, 100000, 512).astype(np.int32))
    return Database({"parent": parent, "child": child})


def test_infer_join_keys_compiled_counters():
    db = _fk_toy_db()
    compiler = PipelineCompiler()
    before = compiler.cache_info()
    fks, cands, checker = infer_join_keys(
        db, profile_database(db), compiler=compiler, use_name_hints=False)
    after = compiler.cache_info()
    assert checker.checks > 0
    assert checker.compiled_checks == checker.checks   # no eager fallback
    assert all(c.compiled for c in cands if c.sampled)
    # every check ran through the pipeline cache (hit or compile miss)
    runs = (after["hits"] + after["misses"]) - (before["hits"]
                                               + before["misses"])
    assert runs == checker.checks
    accepted = {(c.child_table, c.child_col, c.parent_table, c.parent_col)
                for c in fks}
    assert ("child", "fk", "parent", "pid") in accepted
    assert not any(c.child_col == "noise" for c in fks)


def test_infer_eager_path_matches_compiled():
    db = _fk_toy_db()
    profs = profile_database(db)
    fks_c, _, chk_c = infer_join_keys(db, profs,
                                      compiler=PipelineCompiler(),
                                      use_name_hints=False)
    fks_e, _, chk_e = infer_join_keys(db, profs, compiler=None,
                                      use_name_hints=False)
    assert chk_e.compiled_checks == 0
    assert fk_pairs(fks_c) == fk_pairs(fks_e)
    conf_c = {c.pair(): c.confidence for c in fks_c}
    conf_e = {c.pair(): c.confidence for c in fks_e}
    assert conf_c == pytest.approx(conf_e)


# ---------------------------------------------------------------------------
# end-to-end recovery on the anonymized synthetic datasets
# ---------------------------------------------------------------------------

def _dataset(name):
    if name == "dblp":
        from repro.data.dblp import dblp_model, make_dblp
        m = dblp_model()
        return make_dblp(), [m], m.queries()
    if name == "imdb":
        from repro.data.imdb import imdb_model, make_imdb
        m = imdb_model()
        return make_imdb(), [m], m.queries()
    from repro.data.tpcds import (
        CHANNELS,
        combined_model,
        fraud_model,
        make_tpcds,
        recommendation_model,
    )
    truth = ([recommendation_model(ch) for ch in CHANNELS]
             + [fraud_model(ch) for ch in CHANNELS])
    return make_tpcds(sf=10), truth, combined_model().queries()


@pytest.mark.parametrize("name,min_precision,min_recall", [
    ("dblp", 0.8, 1.0),
    ("imdb", 0.99, 0.99),
    ("tpcds", 0.75, 0.9),
])
def test_discovery_recovers_hand_models(name, min_precision, min_recall):
    db, truth_models, hand_queries = _dataset(name)
    adb, mapping = anonymize_columns(db)
    equiv = column_equivalence(adb)
    compiler = PipelineCompiler()
    res = discover(adb, compiler=compiler, use_name_hints=False)

    # acceptance: every containment check ran as a compiled pipeline
    assert res.stats["all_compiled"]
    assert res.stats["pipeline_runs"] == res.stats["containment_checks"]

    pred = canonicalize_pairs(fk_pairs(res.fks), equiv)
    truth = canonicalize_pairs(model_fk_pairs(truth_models, mapping), equiv)
    precision, recall = precision_recall(pred, truth)
    assert precision >= min_precision, sorted(
        tuple(sorted(p)) for p in pred - truth)
    assert recall >= min_recall, sorted(
        tuple(sorted(p)) for p in truth - pred)

    # every hand-written edge query appears among the ranked candidates
    er = edge_recovery(hand_queries, res.edges, mapping, equiv=equiv)
    assert er["recall"] == 1.0, er["missing"]

    # the emitted spec is builder-ready
    model = model_from_spec(res.model_spec(top=5))
    assert len(model.edges) == 5


def test_discovery_with_name_hints_ranks_true_fk_first():
    from repro.data.dblp import make_dblp
    db = make_dblp()
    res = discover(db, compiler=PipelineCompiler(), use_name_hints=True)
    pairs = fk_pairs(res.fks)
    assert frozenset({("paper", "v_sk"), ("venue", "v_id")}) in pairs
    assert frozenset({("wrote", "a_sk"), ("author", "a_id")}) in pairs


# ---------------------------------------------------------------------------
# engine + caching
# ---------------------------------------------------------------------------

def test_engine_discover_caches_results_and_profiles():
    from repro.data.dblp import make_dblp
    eng = ExtractionEngine(make_dblp())
    r1 = eng.discover(use_name_hints=False)
    pipe1 = eng.compiler.cache_info()
    r2 = eng.discover(use_name_hints=False)
    pipe2 = eng.compiler.cache_info()
    assert r2 is r1                                # whole-result cache hit
    # and no new pipeline work ran for the warm call
    assert (pipe2["hits"], pipe2["misses"]) == (pipe1["hits"],
                                                pipe1["misses"])
    info = eng.cache_info()
    assert info["caches"]["discoveries"]["hits"] == 1
    assert info["requests"]["discovers"] == 2

    # different knobs re-run inference but reuse per-table profiles
    r3 = eng.discover(use_name_hints=False, accept_threshold=0.6)
    assert r3 is not r1
    info = eng.cache_info()
    assert info["caches"]["profiles"]["hits"] >= len(r1.profiles)

    # a mutation moves the fingerprint: discovery re-runs, and only the
    # churned table is re-profiled
    eng.db.insert_rows("paper", p_id=np.array([9999], np.int32),
                       v_sk=np.array([0], np.int32),
                       rid=np.array([9999], np.int32))
    r4 = eng.discover(use_name_hints=False)
    assert r4 is not r1
    assert eng.fork(eng.db.snapshot()).discover(
        use_name_hints=False) is r4                # fork inherits the cache


# ---------------------------------------------------------------------------
# satellite: spec round-trip with bit-identical extraction
# ---------------------------------------------------------------------------

def test_discovered_spec_roundtrip_bit_identical():
    from repro.data.dblp import make_dblp
    db = make_dblp()
    res = discover(db, compiler=PipelineCompiler(), use_name_hints=False)
    spec = res.model_spec(top=6)

    m_spec = model_from_spec(spec)
    # hand-build the same model through the fluent builder API
    from repro.api import GraphModelBuilder, join_query
    b = GraphModelBuilder(spec["name"])
    for v in spec["vertices"]:
        b.vertex(v["label"], table=v["table"], id_col=v["id_col"])
    for e in spec["edges"]:
        b.edge(e["label"], src=e["src"], dst=e["dst"],
               query=join_query(e["label"],
                                relations=[tuple(r) for r in e["relations"]],
                                joins=list(e["joins"]),
                                src=e["src_col"], dst=e["dst_col"]))
    m_hand = b.build()

    # and through the JSON serialization loop
    m_json = model_from_json(json.dumps(model_to_spec(m_spec)))

    eng = ExtractionEngine(db)
    fps = [eng.extract(m).graph.fingerprint()
           for m in (m_spec, m_hand, m_json)]
    assert fps[0] == fps[1] == fps[2]


# ---------------------------------------------------------------------------
# serving + HTTP
# ---------------------------------------------------------------------------

def test_service_discover_payload_and_tenant_cache():
    from repro.data.dblp import dblp_model, make_dblp
    from repro.serving import GraphService
    with GraphService(make_dblp(), {"dblp": dblp_model()}) as svc:
        out = svc.discover(use_name_hints=False, top=5)
        assert out["kind"] == "discover" and out["source"] == "computed"
        assert len(out["edges"]) == 5 and len(out["fks"]) >= 5
        assert out["stats"]["all_compiled"]
        json.dumps(out)                           # JSON-clean payload
        # the proposed spec is directly extractable through the service
        m = model_from_spec(out["model_spec"])
        ext = svc.extract(m)
        assert sum(ext["edges"].values()) > 0
        warm = svc.discover(use_name_hints=False, top=5)
        assert warm["source"] == "tenant-cache"


def test_http_discover_endpoint():
    sys.path.insert(0, "examples")
    try:
        from serve_graphs import build_service, make_server
    finally:
        sys.path.pop(0)
    svc = build_service("dblp")
    server = make_server(svc, port=0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    host, port = server.server_address[:2]
    try:
        req = urllib.request.Request(
            f"http://{host}:{port}/v1/discover",
            data=json.dumps({"use_name_hints": False, "top": 5}).encode())
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert resp.status == 200
            out = json.loads(resp.read())
        assert out["kind"] == "discover"
        assert len(out["edges"]) == 5
        assert out["model_spec"]["edges"]
        assert out["stats"]["all_compiled"]
        # the returned spec posts straight back to /v1/extract
        req = urllib.request.Request(
            f"http://{host}:{port}/v1/extract",
            data=json.dumps({"model": out["model_spec"]}).encode())
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert resp.status == 200
            ext = json.loads(resp.read())
        assert ext["kind"] == "extract"
        assert set(ext["edges"]) == {e["label"] for e in out["edges"]}
        assert sum(ext["edges"].values()) > 0
    finally:
        server.shutdown()
        server.server_close()
        svc.close()
        thread.join(10)
