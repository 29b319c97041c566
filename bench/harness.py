"""One run of one cell: data from the seed, set-up, the window, the check.

:func:`run_cell` is the whole run; ``bench/run.py`` is its command line.
The result is the one JSON object the run prints last:

``correct``    every compared number within its limit, every op ok
``attempted``  operations started in the window
``failed``     of those, the ones that raised or reported failure
``metrics``    ``--trace 0``: the cell's end-to-end metrics;
               ``--trace 1``: its per-layer metrics (those whose reader
               found something to read)
``device``     platform, kind and count as JAX reports them, and the peak
               bytes in use on the fullest chip; a traced run adds
               ``busy_s`` and ``window_s``
``breakdown``  traced runs: top device ops, longest idle gaps
``checks``     last: each compared number beside its limit

Set-up is everything from process start to the first timed operation:
generating and loading the tables, the cold operation (plan, compile) and
the traffic's warm-up operations (see ``bench/drive.py``).  The window
then runs operations back to back until ``seconds`` have passed since it
opened; the operation running at that moment completes and counts.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from bench import spec

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_ROOT = os.path.join(spec.ROOT, ".bench_trace")


class NoAccelerator(RuntimeError):
    pass


@dataclasses.dataclass
class Run:
    """What a run saw; per-layer metric readers take it as ``run``."""

    config: dict
    traffic: dict
    tables: dict
    db: object
    model: object
    rng: np.random.Generator
    ops: List[dict] = dataclasses.field(default_factory=list)
    compiles: int = 0
    counters_before: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    counters_after: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    obs_spans: List[dict] = dataclasses.field(default_factory=list)
    trace: object = None
    device_kind: str = ""
    notes: List[str] = dataclasses.field(default_factory=list)

    def peaks(self) -> dict:
        return spec.peaks(self.device_kind)

    def roofline(self, kernel: str):
        return spec.roofline(kernel)

    def per_op(self, value: float) -> float:
        return value / max(1, len(self.ops))

    def span_ms(self, name: str) -> Optional[float]:
        """Milliseconds per operation in the program's spans ``name``
        inside the window; None where there is none."""
        spans = [s["dur_s"] for s in self.obs_spans if s["name"] == name]
        return self.per_op(sum(spans) * 1e3) if spans else None


_COMPILES = [0]          # backend compiles seen by the listener below
_LISTENING = []


def _compile_counter():
    """The process's backend-compile count, listening from the first call
    (JAX offers no way to remove a listener, so it is registered once)."""
    import jax

    if not _LISTENING:
        def on_event(event, _secs, **_):
            if event == BACKEND_COMPILE_EVENT:
                _COMPILES[0] += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)
        _LISTENING.append(on_event)
    return _COMPILES


def _graph_model(graph: dict):
    from repro.core.model import GraphModel

    b = GraphModel.builder(graph["name"])
    for v in graph["vertices"]:
        b.vertex(v["label"], table=v["table"], id_col=v["id"],
                 props=tuple(v["props"]))
    for e in graph["edges"]:
        b.edge(e["label"], src=e["src"], dst=e["dst"],
               relations=[tuple(r) for r in e["relations"]],
               joins=list(e["joins"]), src_col=e["src_col"],
               dst_col=e["dst_col"])
    return b.build()


def _database(tables: dict):
    from repro.core.database import Database
    from repro.relational import Table

    db = Database()
    for name, cols in tables.items():
        db.add_table(name, Table.from_arrays(**cols))
    return db


def _obs_spans(t0: float, t1: float) -> List[dict]:
    from repro import obs

    out = []
    for tid in obs.TRACER.trace_ids():
        for s in obs.TRACER.get(tid) or ():
            if s["start_s"] >= t0 and s["start_s"] < t1:
                out.append(s)
    return out


def _peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True, shrink: int = 1,
             bench: Optional[dict] = None) -> dict:
    """Run one cell once; returns the result object (see module doc).

    ``require_tpu=False`` and ``shrink`` exist for the rehearsal on the
    CPU; the command line always requires the chip and runs full size.
    """
    import jax

    from bench import data

    cell = spec.resolve(bench or spec.load_benchmark(), workload)
    devices = jax.devices()
    chips = int(cell["cell"]["chips"])
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < chips):
        raise NoAccelerator(
            f"{workload} needs {chips} TPU chip(s); JAX has "
            f"{len(devices)} {devices[0].platform} device(s)")
    devices = devices[:chips]

    from repro.core import pipeline
    pipeline.enable_persistent_compilation_cache()
    compiles = _compile_counter()

    config, traffic = cell["config"], cell["traffic"]
    rng = np.random.default_rng(seed)
    tables = data.make_tables(config, seed, shrink)
    run = Run(config=config, traffic=traffic, tables=tables,
              db=_database(tables), model=_graph_model(config["graph"]),
              rng=rng, device_kind=devices[0].device_kind)
    operation = spec.operation(traffic["operation"])
    op = operation.Operation(run, traffic)
    op.setup()
    pipeline.drain_reoptimizations()
    setup_s = time.perf_counter() - t_start

    trace_dir = os.path.join(TRACE_ROOT, str(os.getpid()))
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    run.counters_before = op.counters()
    c0 = compiles[0]
    failed = 0
    w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while not run.ops or time.perf_counter() - w0 < seconds:
            try:
                rec = op.step()
            except Exception as e:      # counted, and the run is incorrect
                print(f"operation failed: {type(e).__name__}: {e}",
                      file=sys.stderr, flush=True)
                rec = {"t0": w0, "t1": time.perf_counter(), "ok": False}
            failed += not rec["ok"]
            run.ops.append(rec)
    w1 = time.perf_counter()
    run.compiles = compiles[0] - c0
    run.counters_after = op.counters()
    run.obs_spans = _obs_spans(w0, w1)
    if trace:
        jax.profiler.stop_trace()
    memory_peak = _peak_bytes(devices)

    checks = operation.compare(run, traffic, op.answers())
    correct = failed == 0 and all(v <= lim for v, lim in checks.values())

    metrics: Dict[str, dict] = {}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    breakdown = None
    if not trace:
        values = {"setup_s": setup_s, **op.end_to_end(run.ops)}
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        from bench import trace as trace_mod

        run.trace = trace_mod.load(trace_dir, run.obs_spans, w0)
        busy = trace_mod.busy_s(run.trace)
        if busy is not None:
            device.update(busy_s=busy, window_s=run.trace.window_s)
            breakdown = {"device_ops": trace_mod.top_ops(run.trace),
                         "idle_gaps": trace_mod.idle_gaps(run.trace)}
        for m in cell["per_layer"]:
            value = spec.metric_reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
        for note in run.notes:
            print(note, file=sys.stderr)

    result = {"correct": bool(correct), "attempted": len(run.ops),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in checks.items()}
    return result


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
