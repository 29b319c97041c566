"""Helpers that operations share.

An operation is ``bench/operations/<name>.py``, found by the name a
traffic file (``bench/traffic/<mix>.json``) gives under ``operation``.
It defines

``Operation(run, params)``  built over the run (tables, database, graph
                            model, seeded generator) and the mix's
                            parameters, with
    ``setup()``             warm every shape the window will use;
    ``step()``              one timed operation, returning its record
                            (``t0``/``t1`` host perf_counter seconds and
                            ``ok``, at least);
    ``counters()``          the program's counters that metrics read;
    ``answers()``           after the window: the sampled answers, on the
                            host; frees the program's state;
    ``end_to_end(ops)``     the mix's end-to-end values over the window's
                            records;
``compare(run, params, answers)``  the answers against the plain
                            reference: ``{name: (value, limit)}``;
``control_answers(run, params, id_dtype, count)``  what the reference
                            answers with ids carried in ``id_dtype``, in
                            the form of ``answers()``: the control, fed
                            through ``compare``.

:class:`Operation` here gives the defaults: no counters, and the
latency statistics that the mix names under ``end_to_end``
(``{"extract_s": "mean"}``, or ``"p95"``), each over every operation of
the window.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import numpy as np

from bench import reference

Checks = Dict[str, Tuple[float, float]]


def annotate(name: str):
    """A host span on the profiler's trace around a call into a layer."""
    return jax.profiler.TraceAnnotation(name)


def latencies(ops: Sequence[dict]) -> np.ndarray:
    return np.array([r["t1"] - r["t0"] for r in ops], np.float64)


def statistic(name: str, values: np.ndarray) -> float:
    """``mean``, or ``p<q>``: the q-th percentile by numpy's ``higher``
    method, so always a sample's value, never an interpolation."""
    if name == "mean":
        return float(np.mean(values))
    if name.startswith("p"):
        return float(np.percentile(values, float(name[1:]),
                                   method="higher"))
    raise ValueError(f"unknown statistic {name!r}")


class Operation:
    def __init__(self, run, params: dict):
        self.run = run
        self.params = params

    def counters(self) -> dict:
        return {}

    def end_to_end(self, ops: Sequence[dict]) -> Dict[str, float]:
        lat = latencies(ops)
        return {name: statistic(stat, lat)
                for name, stat in self.params["end_to_end"].items()}


def host_edges(graph) -> Dict[str, np.ndarray]:
    """Each label's (src, dst) pairs of an extracted graph, on the host."""
    out = {}
    for label, t in graph.edges.items():
        valid = np.asarray(t.valid)
        out[label] = reference.pair_keys(np.asarray(t["src"])[valid],
                                         np.asarray(t["dst"])[valid])
    return out
