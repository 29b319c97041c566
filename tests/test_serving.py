"""Serving layer: coalescing, MVCC snapshot isolation, quotas, HTTP.

The three acceptance properties hammered here with real thread pools:

* K concurrent identical requests execute exactly one extraction —
  everyone else joins the in-flight future (single-flight coalescing).
* A reader pinned to epoch E sees bit-identical graph digests while
  epoch E+1 is being built by a concurrent writer and after the swap.
* A tenant over its quota gets rejections/evictions without touching
  another tenant's admitted requests or cached responses.
"""
import json
import os
import subprocess
import sys
import textwrap
import threading
import time as _time
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.api.engine import ExtractionEngine, _LRUCache
from repro.core.database import Database
from repro.core.model import GraphModel
from repro.relational import Table
from repro.serving import (
    AdmissionError,
    CoalescingScheduler,
    GraphService,
    QuotaExceeded,
    QuotaManager,
    Snapshot,
    SnapshotNotFound,
    SnapshotStore,
    TenantQuota,
    UnknownModel,
)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# tiny dataset: fast enough that every test runs the real engine
# ---------------------------------------------------------------------------

def make_social(n_people=32, n_follows=96, seed=0) -> Database:
    rng = np.random.default_rng(seed)
    person = Table.from_arrays(
        rid=np.arange(n_people, dtype=np.int32),
        p_id=np.arange(n_people, dtype=np.int32),
        age=rng.integers(18, 24, n_people).astype(np.int32))
    follows = Table.from_arrays(
        rid=np.arange(n_follows, dtype=np.int32),
        src_sk=rng.integers(0, n_people, n_follows).astype(np.int32),
        dst_sk=rng.integers(0, n_people, n_follows).astype(np.int32))
    return Database({"person": person, "follows": follows})


def _follows_model(name="social", reverse=False):
    src_col, dst_col = ("P2.p_id", "P1.p_id") if reverse \
        else ("P1.p_id", "P2.p_id")
    return (GraphModel.builder(name)
            .vertex("Person", table="person", id_col="p_id")
            .edge("Follows", src="Person", dst="Person",
                  relations=[("P1", "person"), ("F", "follows"),
                             ("P2", "person")],
                  joins=["P1.p_id = F.src_sk", "F.dst_sk = P2.p_id"],
                  src_col=src_col, dst_col=dst_col)
            .build())


def _sameage_model(name="sameage"):
    return (GraphModel.builder(name)
            .vertex("Person", table="person", id_col="p_id")
            .edge("SameAge", src="Person", dst="Person",
                  relations=[("P1", "person"), ("P2", "person")],
                  joins=["P1.age = P2.age"],
                  src_col="P1.p_id", dst_col="P2.p_id")
            .build())


def _service(**kw) -> GraphService:
    kw.setdefault("compiled", False)   # eager path: no jit warm-up per test
    return GraphService(make_social(), {"social": _follows_model()}, **kw)


def _grow_follows(db_or_service, n=4, seed=7):
    """Insert n fresh follows rows (mutates the live db / via service)."""
    rng = np.random.default_rng(seed)
    if isinstance(db_or_service, GraphService):
        tables = db_or_service._db.tables
        base = int(np.asarray(tables["follows"]["rid"]).max()) + 1
        people = int(np.asarray(tables["person"]["rid"]).max()) + 1
        return db_or_service.mutate("follows", insert={
            "rid": np.arange(base, base + n, dtype=np.int32),
            "src_sk": rng.integers(0, people, n).astype(np.int32),
            "dst_sk": rng.integers(0, people, n).astype(np.int32)})
    db = db_or_service
    base = int(np.asarray(db.tables["follows"]["rid"]).max()) + 1
    people = int(np.asarray(db.tables["person"]["rid"]).max()) + 1
    return db.insert_rows(
        "follows",
        rid=np.arange(base, base + n, dtype=np.int32),
        src_sk=rng.integers(0, people, n).astype(np.int32),
        dst_sk=rng.integers(0, people, n).astype(np.int32))


def _wait_until(cond, timeout=10.0):
    """Spin until ``cond()`` — done-callbacks (tenant-cache records, quota
    releases) run in worker threads just after a future resolves."""
    deadline = _time.monotonic() + timeout
    while not cond():
        if _time.monotonic() > deadline:
            raise AssertionError("condition never became true")
        _time.sleep(0.005)


def _gate_engine_extract(service, epoch=None):
    """Make the served snapshot's extract block until the event is set."""
    with service._store.pin(epoch) as snap:
        engine = snap.engine
    gate = threading.Event()
    real = engine.extract

    def gated(*args, **kwargs):
        assert gate.wait(20), "test gate never opened"
        return real(*args, **kwargs)

    engine.extract = gated
    return gate


# ---------------------------------------------------------------------------
# _LRUCache: access-time (not insertion-time) eviction order
# ---------------------------------------------------------------------------

def test_lru_cache_evicts_by_access_time():
    lru = _LRUCache(2)
    lru.put("a", 1)
    lru.put("b", 2)
    assert lru.get("a") == 1          # touch: "a" is now MRU
    lru.put("c", 3)                   # pressure evicts LRU = "b", not "a"
    assert "a" in lru and "c" in lru and "b" not in lru
    assert lru.info() == {"size": 2, "capacity": 2, "hits": 1,
                          "misses": 0, "evictions": 1}


def test_lru_cache_miss_and_uncounted_get():
    lru = _LRUCache(4)
    assert lru.get("nope") is None and lru.misses == 1
    lru.put("k", "v")
    assert lru.get("k", count=False) == "v"
    assert lru.hits == 0              # bookkeeping scan: no counter skew


def test_csr_cache_hot_entry_survives_cold_pressure():
    """Regression: CSR cache eviction is LRU by access, not insertion.

    A hot graph re-analyzed between cold inserts must survive pressure
    even though it was inserted first.
    """
    db = make_social()
    engine = ExtractionEngine(db, compiled=False, max_csrs=2)
    hot = _follows_model("hot")
    cold1 = _follows_model("cold1", reverse=True)
    cold2 = _sameage_model("cold2")

    engine.analyze(hot, algorithm="degree_stats")     # csrs: [hot]
    engine.analyze(cold1, algorithm="degree_stats")   # csrs: [hot, cold1]
    r = engine.analyze(hot, algorithm="degree_stats")  # touch hot -> MRU
    assert r.provenance.csr_cache_hit
    engine.analyze(cold2, algorithm="degree_stats")   # evicts cold1, NOT hot
    assert engine.cache_info()["caches"]["csrs"]["evictions"] == 1
    assert engine.analyze(
        hot, algorithm="degree_stats").provenance.csr_cache_hit
    assert not engine.analyze(
        cold1, algorithm="degree_stats").provenance.csr_cache_hit


def test_cache_info_shape():
    engine = ExtractionEngine(make_social(), compiled=False)
    engine.extract(_follows_model())
    info = engine.cache_info()
    # flat legacy keys stay (older tests/benchmarks read them)
    for key in ("plans", "views", "csrs", "results", "executables",
                "executable_hits", "executable_misses", "pipeline_retries"):
        assert key in info
    assert info["epoch"] == 0
    for cache in ("plans", "views", "csrs", "results"):
        sub = info["caches"][cache]
        assert set(sub) == {"size", "capacity", "hits", "misses",
                            "evictions", "bytes", "byte_evictions"}
        assert sub["bytes"] == info["cache_bytes"][cache]
    assert info["requests"]["extracts"] == 1
    assert info["requests"]["full_extracts"] == 1


# ---------------------------------------------------------------------------
# CoalescingScheduler
# ---------------------------------------------------------------------------

def test_scheduler_single_flight_coalescing():
    sched = CoalescingScheduler(max_workers=2)
    gate = threading.Event()
    calls = []

    def work():
        gate.wait(10)
        calls.append(1)
        return "payload"

    futs, joined = zip(*[sched.submit_ex("k", work) for _ in range(8)])
    gate.set()
    assert [f.result(10) for f in futs] == ["payload"] * 8
    assert len({id(f) for f in futs}) == 1        # literally the same future
    assert joined == (False,) + (True,) * 7
    assert len(calls) == 1
    st = sched.stats()
    assert (st["submitted"], st["executed"], st["coalesced"]) == (8, 1, 7)
    sched.shutdown()


def test_scheduler_recomputes_after_completion():
    sched = CoalescingScheduler(max_workers=1)
    ran = []
    sched.submit("k", lambda: ran.append(1)).result(10)
    sched.submit("k", lambda: ran.append(1)).result(10)
    assert len(ran) == 2 and sched.stats()["coalesced"] == 0
    sched.shutdown()


def test_scheduler_queue_full_rejects_with_retry_after():
    sched = CoalescingScheduler(max_workers=1, max_queue=2)
    gate = threading.Event()
    f1 = sched.submit("a", gate.wait)     # running
    f2 = sched.submit("b", gate.wait)     # queued; pending = 2 = max_queue
    with pytest.raises(AdmissionError) as err:
        sched.submit("c", gate.wait)
    assert err.value.retry_after > 0
    # coalescing still works while the queue is full: no new work enqueued
    assert sched.submit("a", gate.wait) is f1
    gate.set()
    f1.result(10), f2.result(10)
    assert sched.submit("c", lambda: "ok").result(10) == "ok"
    st = sched.stats()
    assert st["rejected"] == 1 and st["pending"] == 0
    sched.shutdown()


def test_scheduler_failure_shared_and_key_released():
    sched = CoalescingScheduler(max_workers=1)
    gate = threading.Event()

    def boom():
        gate.wait(10)
        raise ValueError("nope")

    f1, j1 = sched.submit_ex("k", boom)
    f2, j2 = sched.submit_ex("k", boom)
    assert f1 is f2 and not j1 and j2
    gate.set()
    with pytest.raises(ValueError):
        f1.result(10)
    assert sched.stats()["failed"] == 1
    # the failed key left the in-flight map: a retry actually re-executes
    assert sched.submit("k", lambda: "fine").result(10) == "fine"
    sched.shutdown()


# ---------------------------------------------------------------------------
# QuotaManager
# ---------------------------------------------------------------------------

def test_quota_inflight_cap_is_per_tenant():
    qm = QuotaManager(default=TenantQuota(max_inflight=2))
    qm.admit("a"), qm.admit("a")
    with pytest.raises(QuotaExceeded) as err:
        qm.admit("a")
    assert err.value.tenant == "a" and err.value.retry_after > 0
    qm.admit("b")                         # other tenant is unaffected
    qm.release("a")
    qm.admit("a")                         # slot freed -> readmitted
    st = qm.stats()
    assert st["a"]["rejections"] == 1 and st["b"]["rejections"] == 0


def test_quota_cache_eviction_stays_inside_tenant():
    qm = QuotaManager(default=TenantQuota(max_entries=2))
    qm.record("big", "shared-key", {"big": 1}, 10)
    for i in range(2):
        qm.record("small", f"k{i}", {"i": i}, 10)
    assert qm.cached("small", "k0") == {"i": 0}   # touch: k0 is MRU
    qm.record("small", "k2", {"i": 2}, 10)        # evicts k1, not k0
    assert qm.cached("small", "k0") is not None
    assert qm.cached("small", "k1") is None
    st = qm.stats()
    assert st["small"]["evictions"] == 1
    # the other tenant's entry never felt the pressure
    assert st["big"]["evictions"] == 0
    assert qm.cached("big", "shared-key") == {"big": 1}


def test_quota_byte_budget():
    qm = QuotaManager(default=TenantQuota(max_entries=99, max_bytes=100))
    qm.record("t", "a", "x", 60)
    qm.record("t", "b", "y", 60)          # 120 bytes > 100 -> evict "a"
    assert qm.cached("t", "a") is None and qm.cached("t", "b") == "y"
    assert qm.stats()["t"]["cache_bytes"] == 60


# ---------------------------------------------------------------------------
# SnapshotStore
# ---------------------------------------------------------------------------

def _snap(epoch):
    db = make_social()
    return Snapshot(epoch=epoch, db=db,
                    engine=ExtractionEngine(db, compiled=False))


def test_snapshot_store_pin_publish_retire():
    store = SnapshotStore(_snap(0), keep=1)
    with store.pin() as s0:
        assert s0.epoch == 0
        store.publish(_snap(1))           # swap while a reader holds epoch 0
        assert store.current_epoch() == 1
        assert s0.pins == 1 and s0.retired
        store.publish(_snap(2))           # epoch 0 pinned -> must survive
        assert store.epochs() == [0, 1, 2]
    store.publish(_snap(3))               # unpinned now: keep=1 drops oldest
    assert 0 not in store.epochs() and store.stats()["dropped"] >= 1


def test_snapshot_store_unknown_and_nonmonotonic():
    store = SnapshotStore(_snap(0))
    store.publish(_snap(2))
    with pytest.raises(SnapshotNotFound) as err:
        store.pin(7).__enter__()
    assert err.value.available == [0, 2]
    assert store.publish(_snap(2)).epoch == 2     # re-publish current: noop
    with pytest.raises(ValueError):
        store.publish(_snap(0))                   # going backwards is a bug


# ---------------------------------------------------------------------------
# GraphService: coalescing, MVCC isolation, tenant quotas
# ---------------------------------------------------------------------------

def test_service_coalesces_concurrent_identical_requests():
    """Acceptance (a): K concurrent identical requests -> 1 extraction."""
    K = 6
    with _service(max_workers=4) as svc:
        gate = _gate_engine_extract(svc)
        pairs = [svc.submit_extract("social") for _ in range(K)]
        gate.set()
        payloads = [fut.result(30) for fut, _ in pairs]
        metas = [meta for _, meta in pairs]
        assert all(p is payloads[0] for p in payloads)   # shared object
        assert [m["coalesced"] for m in metas] == [False] + [True] * (K - 1)
        st = svc.stats()
        assert st["scheduler"]["executed"] == 1
        assert st["scheduler"]["coalesced"] == K - 1
        assert st["engine"]["requests"]["extracts"] == 1
        assert st["engine"]["requests"]["full_extracts"] == 1


def test_service_tenant_cache_serves_repeats():
    with _service() as svc:
        first = svc.extract("social", tenant="t")
        _wait_until(
            lambda: svc.stats()["tenants"]["t"]["cache_entries"] == 1)
        again = svc.extract("social", tenant="t")
        assert first["source"] == "computed"
        assert again["source"] == "tenant-cache"
        assert again["fingerprint"] == first["fingerprint"]
        assert svc.stats()["tenants"]["t"]["hits"] == 1


def test_service_reader_pinned_epoch_is_bit_identical_under_writer():
    """Acceptance (b) + satellite: epoch-E reads identical during E+1 build.

    A writer thread interleaves inserts and refresh() publishes while a
    reader thread hammers extracts pinned to the original epoch — every
    read must return the original graph fingerprint (memoized bag digest
    of every vertex/edge table), during the builds and after the swaps.
    """
    with _service(max_workers=4, keep_snapshots=8) as svc:
        base = svc.extract("social", tenant="reader")
        e0, fp0 = base["epoch"], base["fingerprint"]
        stop = threading.Event()
        failures = []

        def reader():
            while not stop.is_set():
                r = svc.extract("social", tenant="reader", epoch=e0,
                                timeout=30)
                if (r["epoch"], r["fingerprint"]) != (e0, fp0):
                    failures.append(r)
                    return

        threads = [threading.Thread(target=reader) for _ in range(2)]
        for t in threads:
            t.start()
        published = []
        for i in range(3):                       # writer: mutate + publish
            _grow_follows(svc, n=3, seed=100 + i)
            published.append(svc.refresh())
        stop.set()
        for t in threads:
            t.join(60)
        assert not failures, f"pinned reader saw torn state: {failures[0]}"
        assert all(p["path"] == "published" for p in published)

        # after the swaps: latest differs, pinned epoch still bit-identical
        latest = svc.extract("social", tenant="reader")
        assert latest["epoch"] > e0 and latest["fingerprint"] != fp0
        pinned = svc.extract("social", tenant="fresh-tenant", epoch=e0)
        assert pinned["fingerprint"] == fp0
        # parity: the published graph equals a from-scratch oracle extract
        oracle = ExtractionEngine(
            Database(dict(svc._db.tables)), compiled=False).extract(
                _follows_model())
        assert latest["fingerprint"] == oracle.graph.fingerprint()


def test_service_refresh_noop_and_models_paths():
    with _service() as svc:
        assert svc.refresh()["path"] == "noop"
        _grow_follows(svc, n=2)
        out = svc.refresh()
        assert out["path"] == "published"
        assert set(out["models"]) == {"social"}
        assert svc.stats()["served_epoch"] == out["epoch"]


def test_service_quota_rejection_isolated_per_tenant():
    """Acceptance (c): over-quota tenant sheds load; others unaffected."""
    quotas = {"small": TenantQuota(max_inflight=1)}
    with _service(max_workers=4, tenant_quotas=quotas) as svc:
        gate = _gate_engine_extract(svc)
        fut_small, _ = svc.submit_extract("social", tenant="small")
        with pytest.raises(QuotaExceeded) as err:
            svc.submit_extract("social", tenant="small")
        assert err.value.tenant == "small" and err.value.retry_after > 0
        # an unconstrained tenant is admitted and coalesces onto the work
        fut_big, meta_big = svc.submit_extract("social", tenant="big")
        assert meta_big["coalesced"]
        gate.set()
        assert fut_small.result(30) is fut_big.result(30)
        st = svc.stats()["tenants"]
        assert st["small"]["rejections"] == 1
        assert st["big"]["rejections"] == 0 and st["big"]["admitted"] == 1


def test_service_quota_eviction_isolated_per_tenant():
    quotas = {"small": TenantQuota(max_entries=2)}
    with _service(tenant_quotas=quotas) as svc:
        svc.extract("social", tenant="big")
        for method in ("extgraph", "extgraph-oj", "extgraph-mv"):
            svc.extract("social", method=method, tenant="small")
        _wait_until(lambda: svc.stats()["tenants"]["small"]["evictions"] == 1)
        st = svc.stats()["tenants"]
        assert st["small"]["evictions"] == 1
        assert st["small"]["cache_entries"] == 2
        assert st["big"]["evictions"] == 0
        assert svc.extract("social", tenant="big")["source"] == "tenant-cache"


def test_service_admission_backpressure():
    with _service(max_workers=1, max_queue=1) as svc:
        gate = _gate_engine_extract(svc)
        fut, _ = svc.submit_extract("social")
        with pytest.raises(AdmissionError) as err:
            svc.submit_extract("social", method="extgraph-oj")
        assert err.value.retry_after > 0
        gate.set()
        fut.result(30)
        # the rejected caller's quota slot was rolled back at the door
        _wait_until(
            lambda: svc.stats()["tenants"]["public"]["inflight"] == 0)


def test_service_unknown_model_and_analyze():
    with _service() as svc:
        with pytest.raises(UnknownModel):
            svc.extract("nope")
        out = svc.analyze("social", algorithm="pagerank")
        assert out["kind"] == "analyze" and out["algorithm"] == "pagerank"
        assert "digest" in out["values"] and out["values"]["shape"][0] > 0
        # same epoch + params: coalesced-or-cached path, digest identical
        again = svc.analyze("social", algorithm="pagerank")
        assert again["values"]["digest"] == out["values"]["digest"]
        json.dumps(out)                      # payload is JSON-ready


def test_service_stats_shape():
    with _service() as svc:
        svc.extract("social")
        st = svc.stats()
        assert st["served_epoch"] == 0 and st["live_epoch"] == 0
        assert st["models"] == ["social"]
        assert st["scheduler"]["max_workers"] == 4
        assert "caches" in st["engine"] and "requests" in st["engine"]
        assert st["snapshots"]["current_epoch"] == 0
        assert st["persistent_compilation_cache"] is None or \
            isinstance(st["persistent_compilation_cache"], str)


# ---------------------------------------------------------------------------
# HTTP front end (examples/serve_graphs.py)
# ---------------------------------------------------------------------------

class _Http:
    def __init__(self, url, service):
        self.url = url
        self.service = service


@pytest.fixture()
def http_server():
    sys.path.insert(0, "examples")
    try:
        from serve_graphs import make_server
    finally:
        sys.path.pop(0)
    svc = _service(max_workers=2)
    server = make_server(svc, port=0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    host, port = server.server_address[:2]
    try:
        yield _Http(f"http://{host}:{port}", svc)
    finally:
        server.shutdown()
        server.server_close()
        svc.close()
        thread.join(10)


def _http(url, payload=None, headers=None):
    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(url, data=data, headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=60) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_http_extract_mutate_refresh_roundtrip(http_server):
    url = http_server.url
    status, health = _http(f"{url}/healthz")
    assert status == 200 and health["ok"]

    status, models = _http(f"{url}/v1/models")
    assert status == 200 and models["models"] == ["social"]

    status, first = _http(f"{url}/v1/extract", {"model": "social"},
                          headers={"X-Tenant": "alice"})
    assert status == 200 and first["epoch"] == 0
    assert first["tenant"] == "alice"
    assert sum(first["edges"].values()) > 0

    status, out = _http(f"{url}/v1/mutate", {
        "table": "follows",
        "insert": {"rid": [1000, 1001], "src_sk": [0, 1], "dst_sk": [2, 3]}})
    assert status == 200 and out["live_epoch"] > out["served_epoch"]

    status, pub = _http(f"{url}/v1/refresh", {})
    assert status == 200 and pub["path"] == "published"

    status, second = _http(f"{url}/v1/extract", {"model": "social"})
    assert status == 200 and second["epoch"] == pub["epoch"]
    assert second["fingerprint"] != first["fingerprint"]

    # pinned read of the pre-mutation epoch is still served bit-identically
    status, pinned = _http(f"{url}/v1/extract",
                           {"model": "social", "epoch": 0})
    assert status == 200 and pinned["fingerprint"] == first["fingerprint"]

    status, st = _http(f"{url}/v1/stats")
    assert status == 200 and st["served_epoch"] == pub["epoch"]
    assert "alice" in st["tenants"]


def test_http_error_mapping(http_server):
    url = http_server.url
    status, body = _http(f"{url}/v1/extract", {"model": "nope"})
    assert status == 404 and "unknown model" in body["error"]

    status, body = _http(f"{url}/v1/extract", {})
    assert status == 400 and "missing field" in body["error"]

    status, body = _http(f"{url}/v1/extract",
                         {"model": "social", "epoch": 999})
    assert status == 410 and body["available"] == [0]

    status, body = _http(f"{url}/v1/nope", {})
    assert status == 404


def test_http_quota_returns_429_with_retry_after(http_server):
    http_server.service._quotas.set_quota(
        "throttled", TenantQuota(max_inflight=0))
    req = urllib.request.Request(
        f"{http_server.url}/v1/extract",
        data=json.dumps({"model": "social"}).encode(),
        headers={"X-Tenant": "throttled"})
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(req, timeout=60)
    assert err.value.code == 429
    assert float(err.value.headers["Retry-After"]) > 0
    # other tenants keep being served
    status, _ = _http(f"{http_server.url}/v1/extract", {"model": "social"})
    assert status == 200


# ---------------------------------------------------------------------------
# persistent compilation cache: JAX_COMPILATION_CACHE_DIR wins, else the
# checkout's fixed directory (subprocess keeps jax config of the test
# process untouched)
# ---------------------------------------------------------------------------

def test_persistent_compilation_cache_flag(tmp_path):
    code = textwrap.dedent("""
        import jax, sys
        from repro.core.pipeline import (
            enable_persistent_compilation_cache,
            persistent_compilation_cache_dir,
            persistent_compilation_cache_off,
        )
        fixed = sys.argv[1]
        assert persistent_compilation_cache_dir() is None
        assert enable_persistent_compilation_cache() == fixed
        assert persistent_compilation_cache_dir() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert enable_persistent_compilation_cache() == fixed  # idem
        # a cold-start measurement turns the cache off, then back on
        with persistent_compilation_cache_off():
            assert not jax.config.jax_enable_compilation_cache
        assert jax.config.jax_enable_compilation_cache
        assert jax.config.jax_compilation_cache_dir == fixed
        print("OK")
    """)
    env = {**os.environ, "PYTHONPATH": os.path.join(_ROOT, "src")}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    # run from elsewhere: the fixed directory belongs to the checkout
    out = subprocess.run(
        [sys.executable, "-c", code, os.path.join(_ROOT, ".jax_cache")],
        capture_output=True, text=True, env=env, cwd=tmp_path)
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout
    assert not (tmp_path / ".jax_cache").exists()


def test_persistent_cache_env_var_reaches_engine(tmp_path):
    code = textwrap.dedent("""
        import jax, os
        from repro.api.engine import ExtractionEngine
        from repro.core.database import Database
        from repro.core.pipeline import persistent_compilation_cache_dir
        env_dir = os.environ["JAX_COMPILATION_CACHE_DIR"]
        assert persistent_compilation_cache_dir() is None
        ExtractionEngine(Database({}))       # start-up enables the cache
        assert persistent_compilation_cache_dir() == env_dir
        assert jax.config.jax_compilation_cache_dir == env_dir
        jax.jit(lambda x: x * 3 + 1)(2).block_until_ready()
        assert os.listdir(env_dir)           # the compile landed there
        print("OK")
    """)
    cache_dir = str(tmp_path / "env_cache")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": os.path.join(_ROOT, "src"),
             "JAX_COMPILATION_CACHE_DIR": cache_dir})
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout


# ---------------------------------------------------------------------------
# graceful degradation: deadlines, drain-on-close, pin-leak guard
# ---------------------------------------------------------------------------

from repro.durability import FatalFaultInjected, FaultRule, faults  # noqa: E402
from repro.serving import DeadlineExceeded, ServiceClosed  # noqa: E402


def test_scheduler_close_drains_queued_and_coalesced():
    """No follower future is ever left unresolved by close()."""
    sched = CoalescingScheduler(max_workers=1, max_queue=8)
    gate = threading.Event()
    f_lead = sched.submit("slow", lambda: (gate.wait(10), "done")[1])
    followers = [sched.submit("slow", lambda: "x") for _ in range(4)]
    assert all(f is f_lead for f in followers)   # K waiters, one future
    f_queued = sched.submit("other", lambda: "never-runs")

    closer = threading.Thread(target=lambda: sched.close(wait=True))
    closer.start()
    _wait_until(lambda: sched.stats()["closed"])
    gate.set()                                    # let the in-flight finish
    closer.join(10)
    assert not closer.is_alive()

    assert f_lead.result(5) == "done"             # in-flight completed
    with pytest.raises(ServiceClosed):            # queued failed fast
        f_queued.result(5)
    with pytest.raises(ServiceClosed):            # post-close submit
        sched.submit("new", lambda: 1)
    st = sched.stats()
    assert st["pending"] == 0 and st["inflight"] == 0
    assert st["drained"] == 1


def test_scheduler_deadline_admission_and_queue_expiry():
    sched = CoalescingScheduler(max_workers=1, max_queue=8)
    gate = threading.Event()
    sched.submit("slow", lambda: gate.wait(10))

    # admission: estimated queue wait already exceeds the budget
    sched._ewma_s = 5.0
    with pytest.raises(DeadlineExceeded) as err:
        sched.submit("fast", lambda: 1, deadline_s=0.001)
    assert err.value.stage == "admission" and err.value.retry_after > 0

    # queue expiry: admitted optimistically, but the worker frees too late
    sched._ewma_s = 0.0001
    fut = sched.submit("fast2", lambda: 2, deadline_s=0.05)
    _time.sleep(0.12)
    gate.set()
    with pytest.raises(DeadlineExceeded) as err2:
        fut.result(10)
    assert err2.value.stage == "queue"
    st = sched.stats()
    assert st["expired"] == 1 and st["rejected"] >= 1
    assert st["pending"] == 0 and st["inflight"] == 0
    sched.close()


def test_service_close_resolves_every_coalesced_waiter():
    """Satellite regression: K coalesced waiters at close — all resolve."""
    svc = _service(max_workers=1)
    gate = _gate_engine_extract(svc)
    lead, meta = svc.submit_extract("social")
    waiters = [svc.submit_extract("social")[0] for _ in range(4)]
    assert all(w is lead for w in waiters)
    queued, _ = svc.submit_analyze("social", algorithm="degree_stats")
    assert queued is not lead

    closer = threading.Thread(target=svc.close)
    closer.start()
    _wait_until(lambda: svc._scheduler.stats()["closed"])
    gate.set()
    closer.join(15)
    assert not closer.is_alive()

    payload = lead.result(5)                      # leader + followers: data
    assert payload["kind"] == "extract"
    with pytest.raises(ServiceClosed):            # queued-but-unstarted
        queued.result(5)
    with pytest.raises(ServiceClosed):            # terminal for new work
        svc.analyze("social", algorithm="pagerank", iterations=2)
    # every pin released, every quota slot returned
    assert svc._store.pinned_epochs() == []
    _wait_until(lambda: svc._quotas.stats()["public"]["inflight"] == 0)


def test_snapshot_pins_balance_on_every_failure_path():
    """Satellite regression: pins drain on worker faults and deadlines."""
    svc = _service(max_workers=1)
    store = svc._store
    assert "pinned_epochs" in store.stats()

    with store.pin() as snap:                     # live pin is visible
        assert store.pinned_epochs() == [snap.epoch]
    assert store.pinned_epochs() == []

    # failure path 1: the worker raises mid-request
    with faults.inject(FaultRule(site="scheduler.worker",
                                 action="raise_fatal", times=1)):
        with pytest.raises(FatalFaultInjected):
            svc.extract("social", timeout=30)
    assert store.pinned_epochs() == []

    # failure path 2: an admitted request expires in the queue
    gate = _gate_engine_extract(svc)
    lead, _ = svc.submit_extract("social")
    svc._scheduler._ewma_s = 0.0001    # optimistic estimate: admit it
    expired, _ = svc.submit_analyze("social", algorithm="degree_stats",
                                    deadline_s=0.02)
    _time.sleep(0.08)
    gate.set()
    with pytest.raises(DeadlineExceeded):
        expired.result(10)
    lead.result(10)
    _wait_until(lambda: store.pinned_epochs() == [])
    _wait_until(lambda: svc._quotas.stats()["public"]["inflight"] == 0)
    svc.close()


# ---------------------------------------------------------------------------
# HTTP error hygiene: every non-2xx body is {error, retryable, trace_id}
# ---------------------------------------------------------------------------

@pytest.fixture()
def http_server_tight():
    """max_workers=1 / max_queue=2: backpressure is easy to provoke."""
    sys.path.insert(0, "examples")
    try:
        from serve_graphs import make_server
    finally:
        sys.path.pop(0)
    svc = _service(max_workers=1, max_queue=2)
    server = make_server(svc, port=0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    host, port = server.server_address[:2]
    try:
        yield _Http(f"http://{host}:{port}", svc)
    finally:
        faults.uninstall()
        server.shutdown()
        server.server_close()
        svc.close()
        thread.join(10)


def _assert_error_shape(body, retryable, with_retry_after=False):
    assert {"error", "retryable", "trace_id"} <= set(body)
    assert body["retryable"] is retryable
    assert isinstance(body["trace_id"], str) and body["trace_id"]
    if with_retry_after:
        assert body["retry_after"] > 0


def test_http_error_bodies_are_structured(http_server_tight):
    url = http_server_tight.url
    svc = http_server_tight.service

    # quota exhausted -> 429, retryable, Retry-After
    svc._quotas.set_quota("throttled", TenantQuota(max_inflight=0))
    status, body = _http(f"{url}/v1/extract", {"model": "social"},
                         headers={"X-Tenant": "throttled"})
    assert status == 429
    _assert_error_shape(body, True, with_retry_after=True)

    # retired/unpublished epoch -> 410, not retryable
    status, body = _http(f"{url}/v1/extract",
                         {"model": "social", "epoch": 999})
    assert status == 410
    _assert_error_shape(body, False)
    assert body["available"] == [0]

    # unknown model -> 404; bad request -> 400
    status, body = _http(f"{url}/v1/extract", {"model": "nope"})
    assert status == 404
    _assert_error_shape(body, False)
    status, body = _http(f"{url}/v1/extract", {})
    assert status == 400
    _assert_error_shape(body, False)

    # occupy the single worker so queue-level errors are reachable
    gate = _gate_engine_extract(svc)
    results = {}

    def held(name, path, payload):
        results[name] = _http(f"{url}{path}", payload)

    leader = threading.Thread(target=held, args=(
        "leader", "/v1/extract", {"model": "social"}))
    leader.start()
    _wait_until(lambda: svc._scheduler.stats()["pending"] == 1)

    # blown deadline at admission -> 504, retryable
    status, body = _http(f"{url}/v1/extract",
                         {"model": "social", "method": "gqfast",
                          "deadline_s": 0.0001})
    assert status == 504
    _assert_error_shape(body, True, with_retry_after=True)
    assert body["stage"] == "admission"

    # fill the queue, then overflow -> 429, retryable, Retry-After
    waiter = threading.Thread(target=held, args=(
        "waiter", "/v1/analyze",
        {"model": "social", "algorithm": "degree_stats"}))
    waiter.start()
    _wait_until(lambda: svc._scheduler.stats()["pending"] == 2)
    status, body = _http(f"{url}/v1/analyze",
                         {"model": "social", "algorithm": "pagerank"})
    assert status == 429
    _assert_error_shape(body, True, with_retry_after=True)

    gate.set()
    leader.join(30)
    waiter.join(30)
    assert results["leader"][0] == 200 and results["waiter"][0] == 200

    # injected fatal worker fault -> 500, not retryable
    with faults.inject(FaultRule(site="scheduler.worker",
                                 action="raise_fatal", times=1)):
        status, body = _http(f"{url}/v1/analyze",
                             {"model": "social", "algorithm": "pagerank",
                              "params": {"iterations": 3}})
    assert status == 500
    _assert_error_shape(body, False)

    # injected transient worker fault -> 503, retryable
    with faults.inject(FaultRule(site="scheduler.worker",
                                 action="raise", times=1)):
        status, body = _http(f"{url}/v1/extract",
                             {"model": "social", "method": "gqfast"})
    assert status == 503
    _assert_error_shape(body, True)

    # the service is not wedged by any of the above
    status, body = _http(f"{url}/v1/extract", {"model": "social"})
    assert status == 200
    assert svc._store.pinned_epochs() == []
