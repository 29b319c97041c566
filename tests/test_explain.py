"""EXPLAIN / EXPLAIN ANALYZE, device-memory accounting, trajectory gate.

The acceptance bars this file enforces:

* ``engine.explain`` is *free*: it reports the chosen plan — join orders,
  MV-vs-outer-join decision with cost-model numbers, capacities and how
  each was sized, executable-cache state — without running a single
  extract.
* ``engine.explain_analyze`` reports estimated-vs-actual rows and
  capacity utilization for every plan unit of the tpcds/dblp/imdb
  models with **zero added device syncs**: the actuals are recycled from
  the overflow check's single host sync, so an analyzed extract performs
  exactly as many ``pipeline.wait`` host syncs as a plain one.
* cache byte accounting is exact for numpy-backed tables and the
  byte-budget eviction never evicts the sole remaining entry.
* the HTTP front end serves POST /v1/explain and GET /v1/traces, and the
  chrome trace export carries explicit download headers.
* the perf-trajectory ``check()`` gate passes clean records, and fails
  regressed ratios, missing grid cells, and lost breakdowns.
"""
import json
import math
import threading
import urllib.request

import numpy as np
import pytest

from repro import obs
from repro.api import ExtractionEngine
from repro.api.engine import _LRUCache
from repro.core.pipeline import PipelineCompiler


@pytest.fixture(scope="module", params=["tpcds", "dblp", "imdb"])
def dataset(request):
    if request.param == "tpcds":
        from repro.data import fraud_model, make_tpcds
        return request.param, make_tpcds(sf=1), fraud_model("store")
    if request.param == "dblp":
        from repro.data import dblp_model, make_dblp
        return request.param, make_dblp(scale=1), dblp_model()
    from repro.data import imdb_model, make_imdb
    return request.param, make_imdb(scale=1), imdb_model()


def _units(report):
    return list(report.views) + list(report.units)


# -- EXPLAIN: plan visibility without execution ------------------------------

def test_explain_runs_nothing_and_reports_the_plan(dataset):
    name, db, model = dataset
    engine = ExtractionEngine(db.snapshot(), compiler=PipelineCompiler())
    report = engine.explain(model)
    assert engine.cache_info()["requests"].get("full_extracts", 0) == 0
    assert not report.analyzed
    assert report.cost_plan <= report.cost_baseline
    assert math.isfinite(report.sharing_speedup)
    units = _units(report)
    assert units, name
    for u in units:
        assert u.kind in ("view", "edges", "merged")
        assert math.isfinite(u.est_cost) and u.est_cost >= 0
        assert u.executable in ("cached", "uncompiled", "unknown", "eager")
        assert u.capacity_source in ("programs", "memo", "estimated")
        assert len(u.steps) == len(u.capacities)
        for s in u.steps:
            # the static-shape contract: an estimate-sized step is a pow-2
            # bucket; a step with a unique build key holds exactly its
            # probe side's static capacity
            assert s.sizing in ("estimate", "probe_bound")
            if s.sizing == "estimate":
                assert s.capacity > 0 and s.capacity & (s.capacity - 1) == 0
            else:
                assert s.capacity == s.probe_capacity > 0
            assert math.isfinite(s.est_rows) and s.est_rows >= 0
            assert s.actual_rows is None and s.utilization is None
        if u.kind == "merged":
            assert len(u.members) > 1


def test_explain_text_and_json_renderings(dataset):
    _, db, model = dataset
    engine = ExtractionEngine(db.snapshot(), compiler=PipelineCompiler())
    report = engine.explain(model)
    text = report.render_text()
    assert "PLAN" in text and "cost" in text
    for u in _units(report):
        assert u.name in text
    js = json.loads(json.dumps(report.to_json()))
    assert js["model"] == report.model and len(js["units"]) == len(
        report.units)


def test_explain_warms_the_plan_cache_for_the_extract(dataset):
    _, db, model = dataset
    engine = ExtractionEngine(db.snapshot(), compiler=PipelineCompiler())
    assert not engine.explain(model).plan_cache_hit
    before = engine.cache_info()["caches"]["plans"]["hits"]
    engine.extract(model)
    assert engine.cache_info()["caches"]["plans"]["hits"] == before + 1
    assert engine.explain(model).plan_cache_hit


# -- EXPLAIN ANALYZE: actuals for every plan unit, zero added syncs ----------

def test_explain_analyze_reports_actuals_for_every_unit(dataset):
    name, db, model = dataset
    engine = ExtractionEngine(db.snapshot(), compiler=PipelineCompiler())
    report = engine.explain_analyze(model)
    assert report.analyzed
    assert set(report.timings_s) == {"plan", "extract"}
    steps_seen = 0
    for u in _units(report):
        assert u.executable == "cached", (name, u.name)
        assert u.capacity_source in ("programs", "memo"), (name, u.name)
        for s in u.steps:
            steps_seen += 1
            assert s.actual_rows is not None, (name, u.name, s.label)
            assert 0 <= s.actual_rows <= s.capacity
            assert 0.0 <= s.utilization <= 1.0
            assert math.isfinite(s.estimate_ratio) and s.estimate_ratio > 0
    assert steps_seen, name


def _sync_spans():
    spans = obs.TRACER.get(obs.TRACER.trace_ids()[-1])
    return sum(1 for s in spans if s["name"] == "pipeline.wait")


def test_explain_analyze_adds_zero_device_syncs(dataset):
    name, db, model = dataset
    plain = ExtractionEngine(db.snapshot(), compiler=PipelineCompiler())
    plain.extract(model)
    plain_syncs = _sync_spans()
    analyzed = ExtractionEngine(db.snapshot(), compiler=PipelineCompiler())
    analyzed.explain_analyze(model)
    assert plain_syncs > 0, name
    # identical cold pipelines: the analyzed run's actual-rows reporting
    # rides the overflow check's existing host syncs, adding none
    assert _sync_spans() == plain_syncs, name


# -- device-memory accounting ------------------------------------------------

def test_table_byte_accounting_is_exact(dataset):
    _, db, _ = dataset
    tname = sorted(db.tables)[0]
    table = db.tables[tname]
    want = sum(np.asarray(c).nbytes for c in table.columns.values())
    want += np.asarray(table.valid).nbytes
    assert obs.table_nbytes(table) == want
    assert obs.entry_nbytes(table) == want
    assert obs.entry_nbytes(object()) == 0


def test_cache_bytes_surface_after_extract(dataset):
    _, db, model = dataset
    engine = ExtractionEngine(db.snapshot(), compiler=PipelineCompiler())
    engine.extract(model)
    info = engine.cache_info()
    assert set(info["cache_bytes"]) == {"plans", "views", "csrs", "results"}
    assert info["cache_bytes"]["results"] > 0
    assert isinstance(info["device_memory"], dict)
    assert obs.REGISTRY.value("engine_cache_bytes", cache="results") == \
        info["cache_bytes"]["results"]


def test_lru_byte_budget_eviction_keeps_one_entry():
    cache = _LRUCache(10, name="unit-test", sizer=len, max_bytes=100)
    cache.put("a", b"x" * 60)
    cache.put("b", b"y" * 60)          # 120 > 100: evicts "a"
    assert cache.get("a") is None and cache.get("b") is not None
    assert cache.bytes == 60
    info = cache.info()
    assert info["bytes"] == 60 and info["max_bytes"] == 100
    assert info["byte_evictions"] == 1
    # a single over-budget value must still cache (floor of one entry)
    cache.put("huge", b"z" * 500)
    assert cache.get("huge") is not None and len(cache) == 1
    assert cache.bytes == 500
    cache.pop("huge")
    assert cache.bytes == 0


def test_engine_byte_budget_bounds_result_cache(dataset):
    _, db, model = dataset
    engine = ExtractionEngine(db.snapshot(), compiler=PipelineCompiler(),
                              cache_byte_budgets={"results": 1})
    engine.extract(model)
    info = engine.cache_info()
    # one result always stays resident (the floor), nothing beyond it
    assert info["caches"]["results"]["size"] == 1
    assert info["caches"]["results"]["max_bytes"] == 1


def test_device_memory_stats_shape():
    stats = obs.device_memory_stats(gauges=False)
    assert isinstance(stats, dict)
    for per_device in stats.values():
        assert set(per_device) <= {"in_use", "peak", "limit"}


# -- HTTP: /v1/explain, /v1/traces, chrome export headers --------------------

def test_http_explain_traces_and_chrome_headers():
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                           / "examples"))
    try:
        from serve_graphs import make_server
    finally:
        sys.path.pop(0)
    from repro.data import dblp_model, make_dblp
    from repro.serving import GraphService
    svc = GraphService(make_dblp(scale=1), {"dblp": dblp_model()},
                       max_workers=2)
    server = make_server(svc)
    host, port = server.server_address[:2]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    base = f"http://{host}:{port}"
    try:
        req = urllib.request.Request(
            base + "/v1/explain", data=b'{"model": "dblp"}',
            headers={"X-Request-Id": "explain-1"})
        with urllib.request.urlopen(req) as r:
            out = json.loads(r.read())
        assert out["kind"] == "explain" and not out["analyze"]
        assert "PLAN" in out["text"]
        assert out["report"]["units"], out["report"]

        req = urllib.request.Request(
            base + "/v1/explain",
            data=b'{"model": "dblp", "analyze": true}',
            headers={"X-Request-Id": "explain-2"})
        with urllib.request.urlopen(req) as r:
            analyzed = json.loads(r.read())
        assert analyzed["analyze"]
        steps = [s for u in (analyzed["report"]["views"]
                             + analyzed["report"]["units"])
                 for s in u["steps"]]
        assert steps and all(s["actual_rows"] is not None for s in steps)

        with urllib.request.urlopen(base + "/v1/traces?limit=5") as r:
            listing = json.loads(r.read())
        assert listing["traces"], listing
        by_id = {t["trace_id"]: t for t in listing["traces"]}
        assert "explain-2" in by_id
        for t in listing["traces"]:
            assert {"trace_id", "root", "category", "wall_s",
                    "spans", "dropped"} <= set(t)

        with urllib.request.urlopen(
                base + "/v1/trace/explain-2?format=chrome") as r:
            assert r.headers["Content-Type"].startswith("application/json")
            disposition = r.headers["Content-Disposition"]
            assert disposition == ('attachment; '
                                   'filename="trace-explain-2.json"')
            chrome = json.loads(r.read())
        assert chrome["traceEvents"]
    finally:
        server.shutdown()
        svc.close()


# -- trajectory regression gate ----------------------------------------------

def _cell(sf, churn, conc, **over):
    rec = {"sf": sf, "churn": churn, "concurrency": conc,
           "warm_speedup": 100.0, "refresh_speedup": 10.0,
           "throughput_scaling": 2.0,
           "breakdown": {"wall_s": 1.0, "compile_s": 0.5}}
    rec.update(over)
    return rec


def test_trajectory_check_gate(tmp_path):
    from benchmarks import trajectory
    baseline = [_cell(1, 0.0, 1, refresh_speedup=None),
                _cell(1, 0.01, 4)]
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps(baseline))

    clean = [_cell(1, 0.0, 1, refresh_speedup=None), _cell(1, 0.01, 4)]
    assert trajectory.check(clean, str(path), rel_tol=0.5) == []

    # a ratio below baseline * (1 - tol) fails with a readable message
    slow = [_cell(1, 0.0, 1, refresh_speedup=None),
            _cell(1, 0.01, 4, warm_speedup=40.0)]
    failures = trajectory.check(slow, str(path), rel_tol=0.5)
    assert len(failures) == 1 and "warm_speedup" in failures[0]

    # shrinking the grid or losing the breakdown is itself a regression
    failures = trajectory.check(clean[:1], str(path), rel_tol=0.5)
    assert any("missing grid cells" in f for f in failures)
    broken = [_cell(1, 0.0, 1, refresh_speedup=None),
              _cell(1, 0.01, 4, breakdown=None,
                    throughput_scaling=float("nan"))]
    failures = trajectory.check(broken, str(path), rel_tol=0.5)
    assert any("breakdown" in f for f in failures)
    assert any("not finite" in f for f in failures)
