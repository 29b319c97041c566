"""Drive the graph system's main path once on one TPU chip, and check it.

    python chip_smoke.py

Run from the root of a checkout on a machine with one TPU chip.  In one
process, through the entry points a user calls:

(a) TPC-DS at published fact row counts (``make_tpcds(sf=1000)``: 2.88M
    store_sales rows), the fraud model: cold and warm
    ``ExtractionEngine.extract``, bag digests against an engine whose
    compiler runs no Pallas kernel, then ``analyze`` PageRank, WCC and
    2-hop through the Pallas edge kernels against the numpy oracles of
    ``repro.graph.reference``;
(b) DBLP (``make_dblp(scale=100)``: 1.8M wrote rows), the co-author model
    (the JS-MV view path): cold and warm extract, same digest check;
(c) ``GraphService`` over the (a) data: a 1% store_sales churn through
    ``mutate``, ``refresh`` on the delta path, the published fingerprint
    against a from-scratch extract, then a few served reads.

Every phase prints its wall time, XLA compile count and device
``peak_bytes_in_use``.  The script exits non-zero, printing no result,
when JAX finds no TPU or any check fails; otherwise its last line is
``{"ok": true, "device": {...}}`` with the device as JAX reports it.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

TPCDS_SF = 1000
DBLP_SCALE = 100
CHURN = 0.01
PAGERANK_ITERS = 5
KHOP_K = 2
# PageRank runs in f32 on the chip against the oracle's f64: a relative
# bound, plus an absolute one at 1e-3 of the uniform rank 1/V
PAGERANK_RTOL = 1e-3
PAGERANK_ATOL_PER_V = 1e-3


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


class Phase:
    """Wall time, backend compiles and device peak bytes of one phase."""

    compiles = 0

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        import jax
        print(f"== phase {self.name}", flush=True)
        self.c0 = Phase.compiles
        self.t0 = time.perf_counter()
        self.device = jax.devices()[0]
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter() - self.t0
        stats = self.device.memory_stats() or {}
        print(f"   phase {self.name}: wall_s={wall:.3f} "
              f"compiles={Phase.compiles - self.c0} "
              f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}",
              flush=True)
        return False


def _count_compiles() -> None:
    import jax
    from jax._src.dispatch import BACKEND_COMPILE_EVENT

    def on_event(event, _secs, **_):
        if event == BACKEND_COMPILE_EVENT:
            Phase.compiles += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)


def _host_coo(csr):
    src, dst, valid = csr.coo(None)
    return np.asarray(src), np.asarray(dst), np.asarray(valid)


def _extract_pair(db, model, use_kernel):
    """Cold + warm extract on the kernel path; digest vs the jnp path."""
    from repro.api import ExtractionEngine
    from repro.core.pipeline import PipelineCompiler

    engine = ExtractionEngine(db, compiler=PipelineCompiler(
        use_kernel=use_kernel))
    check(engine.compiler.use_kernel, "compiler.use_kernel is on")
    t0 = time.perf_counter()
    cold = engine.extract(model)
    cold.graph.block_until_ready()
    print(f"   cold extract {time.perf_counter() - t0:.3f}s "
          f"views_built={list(cold.provenance.views_built)}", flush=True)
    t0 = time.perf_counter()
    warm = engine.extract(model)
    warm.graph.block_until_ready()
    print(f"   warm extract {time.perf_counter() - t0:.3f}s", flush=True)
    check(warm.provenance.plan_cache_hit, "warm extract hits the plan cache")
    edges = {k: int(np.asarray(t.valid).sum())
             for k, t in warm.graph.edges.items()}
    print(f"   edges {edges}", flush=True)
    check(all(n > 0 for n in edges.values()), "every edge label has edges")
    ref = ExtractionEngine(db, compiler=PipelineCompiler(use_kernel=False))
    ref_fp = ref.extract(model).graph.fingerprint()
    check(cold.graph.fingerprint() == ref_fp
          and warm.graph.fingerprint() == ref_fp,
          f"cold/warm bag digests equal the jnp-path engine's ({ref_fp})")
    return engine, cold


def _analytics(engine, model, use_kernel):
    from repro import obs
    from repro.graph import reference

    pr = engine.analyze(model, algorithm="pagerank", iters=PAGERANK_ITERS,
                        use_kernel=use_kernel)
    n = pr.csr.num_vertices
    src, dst, valid = _host_coo(pr.csr)
    print(f"   V={n} E={int(valid.sum())} "
          f"pagerank {pr.timings.analyze_s:.3f}s", flush=True)
    want = reference.pagerank_np(src, dst, valid, n, iters=PAGERANK_ITERS)
    got = np.asarray(pr.values)
    err = float(np.max(np.abs(got - want)))
    check(np.allclose(got, want, rtol=PAGERANK_RTOL,
                      atol=PAGERANK_ATOL_PER_V / n),
          f"pagerank matches pagerank_np (max abs err {err:.3e})")

    wcc = engine.analyze(model, algorithm="wcc", use_kernel=use_kernel)
    print(f"   wcc {wcc.timings.analyze_s:.3f}s", flush=True)
    check(np.array_equal(np.asarray(wcc.values),
                         reference.wcc_np(src, dst, valid, n)),
          "wcc equals wcc_np exactly")

    seeds = np.unique(np.concatenate([src[valid][:2], [0, n - 1]]))
    kh = engine.analyze(model, algorithm="khop", seeds=seeds, k=KHOP_K,
                        use_kernel=use_kernel)
    print(f"   khop {kh.timings.analyze_s:.3f}s", flush=True)
    seed_mask = np.zeros(n, bool)
    seed_mask[seeds] = True
    want_kh = reference.khop_np(src, dst, valid, seed_mask, n, k=KHOP_K)
    check(np.array_equal(np.asarray(kh.values), want_kh),
          f"{KHOP_K}-hop equals khop_np exactly "
          f"({int((want_kh > 0).sum())} reached)")
    runs = obs.REGISTRY.value("graph_algorithm_runs_total",
                              algorithm="pagerank", kernel="pallas")
    check(runs > 0, f'graph_algorithm_runs_total{{kernel="pallas"}} = {runs}')


def _served(db, model, use_kernel):
    from repro.api import ExtractionEngine
    from repro.core.database import Database
    from repro.core.pipeline import PipelineCompiler
    from repro.serving import GraphService

    from benchmarks.trajectory import _churn

    name = "fraud_store"
    svc = GraphService(db, {name: model},
                       compiler=PipelineCompiler(use_kernel=use_kernel),
                       max_workers=2)
    try:
        fp0 = svc.extract(name)["fingerprint"]
        changed = _churn(svc, np.random.default_rng(7), CHURN)
        t0 = time.perf_counter()
        out = svc.refresh()
        print(f"   refresh of {changed} changed rows "
              f"{time.perf_counter() - t0:.3f}s: {out}", flush=True)
        check(out["path"] == "published"
              and out["models"] == {name: "delta"},
              "refresh published on the delta path")
        got = svc.extract(name)
        scratch = ExtractionEngine(Database(dict(svc._db.tables)),
                                   compiler=PipelineCompiler(
                                       use_kernel=False))
        want = scratch.extract(model).graph.fingerprint()
        check(got["fingerprint"] == want and want != fp0,
              f"published fingerprint equals a from-scratch extract "
              f"({want})")
        for _ in range(2):
            r = svc.extract(name)
            check(r["fingerprint"] == want, "served extract read")
        for algorithm, params in (("degree_stats", {}),
                                  ("khop", {"seeds": [0, 1], "k": KHOP_K})):
            r = svc.analyze(name, algorithm=algorithm,
                            use_kernel=use_kernel, **params)
            check(r["fingerprint"] == want,
                  f"served {algorithm} read at epoch {r['epoch']}")
    finally:
        svc.close()


def _tpu_custom_call_in_units() -> bool:
    from repro.core import pipeline

    with pipeline._CACHE_LOCK:
        exes = list(pipeline._EXECUTABLE_CACHE.values())
    return any("tpu_custom_call" in exe.as_text() for exe in exes)


def main(tpcds_sf: int = TPCDS_SF, dblp_scale: int = DBLP_SCALE,
         use_kernel=None, require_tpu: bool = True) -> dict:
    """Run phases (a)-(c); returns the device record or raises.

    The arguments exist for a rehearsal at small sizes on the CPU
    (``use_kernel=True`` runs the kernels in interpret mode,
    ``require_tpu=False`` skips the checks only a chip can pass); the
    command line always runs the full sizes on the chip.
    """
    import jax

    dev = jax.devices()[0]
    if require_tpu and dev.platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX's first device is {dev.platform}")
    _count_compiles()

    from repro import obs
    from repro.core.pipeline import (
        REOPT_FAILURES,
        drain_reoptimizations,
        persistent_compilation_cache_dir,
    )
    from repro.data import dblp_model, fraud_model, make_dblp, make_tpcds

    with Phase("a: tpcds fraud extract"):
        db = make_tpcds(sf=tpcds_sf, seed=0)
        print(f"   store_sales rows {db.stats['store_sales'].rows}",
              flush=True)
        model = fraud_model("store")
        engine, _ = _extract_pair(db, model, use_kernel)
    print(f"   compile cache {persistent_compilation_cache_dir()}",
          flush=True)
    with Phase("a: tpcds fraud analyze"):
        _analytics(engine, model, use_kernel)
    with Phase("b: dblp extract"):
        dblp = make_dblp(scale=dblp_scale)
        print(f"   wrote rows {dblp.stats['wrote'].rows}", flush=True)
        _extract_pair(dblp, dblp_model(), use_kernel)
    with Phase("c: served refresh"):
        _served(db, model, use_kernel)

    drain_reoptimizations()
    failures = obs.REGISTRY.value(REOPT_FAILURES)
    check(failures == 0, f"background recompile failures = {failures:g}")
    if require_tpu:
        check(_tpu_custom_call_in_units(),
              "tpu_custom_call in a compiled plan unit")
        hbm = obs.device_memory_stats(gauges=False)
        check(bool(hbm) and all("peak" in v for v in hbm.values()),
              f"HBM stats reported: {hbm}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


if __name__ == "__main__":
    try:
        device = main()
    except Exception as e:           # any failed phase: no result line
        import traceback
        traceback.print_exc()
        print(f"chip_smoke FAILED: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        sys.stdout.flush()
        os._exit(1)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    # background compile threads can crash interpreter teardown
    os._exit(0)
