"""Observability layer: metrics registry, tracer, and end-to-end wiring.

Covers the contracts the instrumentation is built on:

* counters are exact under a thread hammer (locked adds, no lost updates),
* histogram memory is bounded by construction whatever is observed,
* eager and compiled engines emit the same structural span tree,
* K coalesced requests produce one leader trace and K-1 follower spans
  linked to it,
* spans reach a profiler trace's host plane by name, and the ``bench.``
  prefix is refused,
* a served extract's trace attributes >= 95% of its wall time, and the
  HTTP front end round-trips /v1/trace and /v1/metrics (JSON + a
  parseable Prometheus text format).
"""
import json
import math
import threading
import time
import urllib.request

import pytest

from repro import obs
from repro.obs.metrics import MetricsRegistry, _NBUCKETS
from repro.obs.trace import Tracer


# -- metrics registry --------------------------------------------------------

def test_counter_exact_under_thread_hammer():
    reg = MetricsRegistry()
    threads, per_thread = 8, 10_000
    c = reg.counter("hammer_total", event="inc")

    def hammer():
        for _ in range(per_thread):
            c.inc()

    ts = [threading.Thread(target=hammer) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert reg.value("hammer_total", event="inc") == threads * per_thread


def test_labeled_series_are_independent():
    reg = MetricsRegistry()
    reg.counter("events_total", kind="a").inc(3)
    reg.counter("events_total", kind="b").inc()
    assert reg.value("events_total", kind="a") == 3
    assert reg.value("events_total", kind="b") == 1
    assert reg.value("events_total", kind="missing") == 0.0
    # same name, different kind: typed families reject the re-registration
    with pytest.raises(ValueError):
        reg.gauge("events_total")


def test_histogram_memory_bounded_and_quantiles_sane():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds")
    # 100k observations over ~19 decades, incl. zero/negative/huge
    for i in range(100_000):
        h.observe((i % 997) * 1e-6)
    h.observe(0.0)
    h.observe(-5.0)
    h.observe(1e12)
    assert h.count == 100_003
    # bounded by construction: fixed bucket array, never raw samples
    assert len(h._buckets) == _NBUCKETS
    snap = h.snapshot()
    assert snap["min"] == -5.0 and snap["max"] == 1e12
    # quantiles are bucket estimates: within 2x of the true p50 (~498us)
    assert 2.5e-4 <= snap["p50"] <= 1e-3
    assert math.isfinite(snap["mean"])


def test_prometheus_text_format_parses():
    reg = MetricsRegistry()
    reg.counter("req_total", help="requests", path="extract").inc(5)
    reg.gauge("depth", queue="serving").set(2)
    h = reg.histogram("lat_seconds")
    for v in (0.001, 0.002, 0.004, 1.5):
        h.observe(v)
    text = reg.to_prometheus()
    samples = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        samples[name] = float(value)   # every sample line parses
    assert samples['req_total{path="extract"}'] == 5
    assert samples['depth{queue="serving"}'] == 2
    assert samples["lat_seconds_count"] == 4
    assert samples['lat_seconds_bucket{le="+Inf"}'] == 4
    # cumulative le series is monotone
    buckets = [(k, v) for k, v in samples.items()
               if k.startswith("lat_seconds_bucket") and "+Inf" not in k]
    cums = [v for _, v in sorted(buckets)]
    assert cums == sorted(cums)


# -- tracer ------------------------------------------------------------------

def test_span_nesting_ids_and_summary():
    tr = Tracer()
    with tr.span("root") as root:
        with tr.span("child", category="execute"):
            time.sleep(0.01)
    spans = {s["name"]: s for s in tr.get(root.trace_id)}
    assert set(spans) == {"root", "child"}
    assert spans["child"]["parent"] == spans["root"]["id"]
    assert spans["child"]["trace"] == root.trace_id
    s = tr.summary(root.trace_id)
    assert s["root"] == "root"
    assert s["by_category_s"]["execute"] >= 0.009
    assert s["coverage"] >= 0.95


def test_trace_ring_buffer_is_bounded():
    tr = Tracer(max_traces=4, max_spans=8)
    for i in range(10):
        with tr.span(f"t{i}"):
            pass
    assert len(tr.trace_ids()) == 4          # LRU-evicted, never unbounded
    with tr.span("big") as big:
        for _ in range(20):
            with tr.span("leaf"):
                pass
    assert len(tr.get(big.trace_id)) == 8    # per-trace span cap
    assert tr.dropped(big.trace_id) > 0


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    with tr.span("x") as sp:
        sp.set(a=1)
    assert tr.trace_ids() == []
    assert sp.trace_id == ""


# -- engine wiring -----------------------------------------------------------

@pytest.fixture(scope="module")
def dblp():
    from repro.data import make_dblp
    from repro.data.dblp import dblp_model
    return make_dblp(scale=1), dblp_model()


def _last_trace():
    return obs.TRACER.get(obs.TRACER.trace_ids()[-1])


def test_eager_and_compiled_emit_same_span_shape(dblp):
    from repro.api import ExtractionEngine
    db, model = dblp
    ExtractionEngine(db).extract(model)
    compiled_shape = obs.span_tree_shape(_last_trace())
    ExtractionEngine(db.snapshot(), compiled=False).extract(model)
    eager_shape = obs.span_tree_shape(_last_trace())
    assert compiled_shape == eager_shape
    names = str(compiled_shape)
    assert "plan" in names and "vertices" in names


def test_traced_call_breakdown_fields(dblp):
    from repro.api import ExtractionEngine
    db, model = dblp
    engine = ExtractionEngine(db.snapshot())
    _, bd = obs.traced_call("t", engine.extract, model)
    for key in ("wall_s", "plan_s", "compile_s", "execute_s", "transfer_s",
                "csr_s", "queue_s", "coverage"):
        assert math.isfinite(bd[key]), (key, bd)
    assert bd["coverage"] >= 0.95


def test_spans_land_on_the_profiler_host_plane(dblp, tmp_path):
    """Under a profiler trace, a warm extract's spans appear on the
    ``.xplane.pb`` host plane by name, on the profiler's clock."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro.api import ExtractionEngine
    db, model = dblp
    engine = ExtractionEngine(db.snapshot())
    engine.extract(model)
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.extract(model)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    units = {n for n in names if n.startswith("unit:")}
    assert {"engine.extract", "plan", "pipeline.dispatch",
            "pipeline.wait"} <= names
    assert units and all(u.split(":", 1)[1] for u in units)


def test_bench_prefix_is_reserved():
    t = Tracer()
    with pytest.raises(ValueError):
        t.span("bench.window")
    with pytest.raises(ValueError):
        t.record("bench.op", 0.0, 1.0)
    with t.span("benchmark.ok"):
        pass


# -- serving: coalescing + trace links ---------------------------------------

def test_coalesced_requests_link_leader_trace(dblp):
    from repro.serving import GraphService
    db, model = dblp
    svc = GraphService(db.snapshot(), {"dblp": model}, max_workers=2)
    try:
        # K submits from one thread while the leader's cold extract is in
        # flight: exactly one computes, the rest join its future
        K = 5
        futs = [svc.submit_extract("dblp", tenant=f"t{i}",
                                   request_id=f"req-{i}")
                for i in range(K)]
        for fut, _ in futs:
            fut.result(timeout=300)
        metas = [meta for _, meta in futs]
        joined = [m for m in metas if m["coalesced"]]
        leaders = [m for m in metas if not m["coalesced"]]
        assert len(leaders) == 1 and len(joined) == K - 1
        leader_tid = leaders[0]["trace_id"]
        assert leader_tid == "req-0"
        assert all(m["leader_trace_id"] == leader_tid for m in joined)
        # leader trace covers the full request; each follower's own trace
        # is a single queue-span linked to the leader (done-callbacks may
        # land just after result(), so poll briefly)
        leader_names = {s["name"] for s in obs.TRACER.get(leader_tid)}
        assert "serve.extract" in leader_names
        assert "engine.extract" in leader_names
        for m in joined:
            deadline = time.time() + 5
            spans = obs.TRACER.get(m["trace_id"])
            while not spans and time.time() < deadline:
                time.sleep(0.01)
                spans = obs.TRACER.get(m["trace_id"])
            assert spans and spans[0]["name"] == "coalesced.follow"
            assert spans[0]["attrs"]["links"] == leader_tid
            assert spans[0]["category"] == "queue"
    finally:
        svc.close()


# -- serving: HTTP round-trip ------------------------------------------------

def test_served_trace_coverage_and_http_roundtrip(dblp):
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                           / "examples"))
    try:
        from serve_graphs import make_server
    finally:
        sys.path.pop(0)
    from repro.serving import GraphService
    db, model = dblp
    svc = GraphService(db.snapshot(), {"dblp": model}, max_workers=2)
    server = make_server(svc)
    host, port = server.server_address[:2]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    base = f"http://{host}:{port}"
    try:
        req = urllib.request.Request(
            base + "/v1/extract", data=b'{"model": "dblp"}',
            headers={"X-Request-Id": "http-req-1"})
        with urllib.request.urlopen(req) as r:
            out = json.loads(r.read())
        assert out["trace_id"] == "http-req-1"

        with urllib.request.urlopen(base + "/v1/trace/http-req-1") as r:
            tr = json.loads(r.read())
        summary = tr["summary"]
        assert summary["root"] == "serve.extract"
        # the acceptance bar: attributed plan/compile/execute/csr/queue
        # time covers >= 95% of the served request's wall time
        assert summary["coverage"] >= 0.95
        cats = summary["by_category_s"]
        assert set(cats) >= {"plan", "compile", "execute", "queue"}

        with urllib.request.urlopen(
                base + "/v1/trace/http-req-1?format=chrome") as r:
            chrome = json.loads(r.read())
        assert {e["ph"] for e in chrome["traceEvents"]} == {"X"}

        with urllib.request.urlopen(base + "/v1/metrics") as r:
            snap = json.loads(r.read())
        assert "serving_requests_total" in snap

        with urllib.request.urlopen(
                base + "/v1/metrics?format=prometheus") as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
        for line in text.splitlines():
            if line and not line.startswith("#"):
                float(line.rpartition(" ")[2])   # every sample parses

        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/v1/trace/no-such-trace")
        assert ei.value.code == 404
    finally:
        server.shutdown()
        svc.close()


# -- concurrent observability reads ------------------------------------------

def test_observability_reads_consistent_under_load(dblp):
    """Readers hammer /v1/metrics (both formats) and stats()/cache_info()
    while extract, mutate, and refresh requests run: no exceptions, no torn
    snapshots (every family renders with its full shape), and the request
    counters stay exact — one increment per submitted extract."""
    import pathlib
    import sys
    import urllib.error
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                           / "examples"))
    try:
        from serve_graphs import make_server
    finally:
        sys.path.pop(0)
    import numpy as np
    from repro.serving import GraphService
    db, model = dblp
    svc = GraphService(db.snapshot(), {"dblp": model}, max_workers=4)
    server = make_server(svc)
    host, port = server.server_address[:2]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    base = f"http://{host}:{port}"

    def our_requests():
        fam = obs.REGISTRY.snapshot().get("serving_requests_total")
        if not fam:
            return 0.0
        return sum(s["value"] for s in fam["series"]
                   if s["labels"].get("kind") == "extract"
                   and s["labels"].get("tenant", "").startswith("obsload-"))

    before = our_requests()
    stop = threading.Event()
    errors = []

    def reader():
        while not stop.is_set():
            try:
                with urllib.request.urlopen(base + "/v1/metrics") as r:
                    snap = json.loads(r.read())
                for fam in snap.values():     # untorn: full family shape
                    assert {"type", "help", "series"} <= set(fam)
                    for series in fam["series"]:
                        assert "labels" in series
                with urllib.request.urlopen(
                        base + "/v1/metrics?format=prometheus") as r:
                    for line in r.read().decode().splitlines():
                        if line and not line.startswith("#"):
                            float(line.rpartition(" ")[2])
                stats = svc.stats()
                info = stats["engine"]
                assert {"caches", "cache_bytes", "requests"} <= set(info)
                assert set(info["cache_bytes"]) == {"plans", "views",
                                                    "csrs", "results"}
            except Exception as e:            # pragma: no cover - fail path
                errors.append(e)
                return

    N_EXTRACTORS, PER = 3, 6

    def extractor(i):
        try:
            for _ in range(PER):
                svc.extract("dblp", tenant=f"obsload-{i}", timeout=300)
        except Exception as e:
            errors.append(e)

    def churner():
        try:
            rng = np.random.default_rng(7)
            for round_no in range(3):
                base_rid = 10_000_000 + round_no * 100
                svc.mutate("wrote", insert={
                    "rid": np.arange(base_rid, base_rid + 50,
                                     dtype=np.int32),
                    "a_sk": rng.integers(0, 100, 50).astype(np.int32),
                    "p_sk": rng.integers(0, 100, 50).astype(np.int32)})
                svc.refresh()
        except Exception as e:
            errors.append(e)

    readers = [threading.Thread(target=reader) for _ in range(2)]
    writers = ([threading.Thread(target=extractor, args=(i,))
                for i in range(N_EXTRACTORS)]
               + [threading.Thread(target=churner)])
    try:
        for t in readers + writers:
            t.start()
        for t in writers:
            t.join()
        stop.set()
        for t in readers:
            t.join()
        assert not errors, errors
        assert our_requests() - before == N_EXTRACTORS * PER
    finally:
        stop.set()
        server.shutdown()
        svc.close()
