"""Warm ``ExtractionEngine.extract`` of the whole graph model, back to
back.  Sampled answers: one of the first three, drawn from the seed, and
the last one of the window; each is compared with the reference's bag of
edges per label."""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from bench import drive, reference


class Operation(drive.Operation):
    def __init__(self, run, params: dict):
        super().__init__(run, params)
        self.keep_index = int(run.rng.integers(0, 3))
        self.kept: Dict[int, object] = {}
        self.n = 0

    def setup(self) -> None:
        from repro.api import ExtractionEngine

        self.engine = ExtractionEngine(self.run.db)
        self.engine.extract(self.run.model)
        self.engine.extract(self.run.model)

    def step(self) -> dict:
        t0 = time.perf_counter()
        with drive.annotate("bench.extract"):
            res = self.engine.extract(self.run.model)
        t1 = time.perf_counter()
        if self.n == self.keep_index:
            self.kept[self.n] = res.graph
        self.last = (self.n, res.graph)
        self.n += 1
        return {"t0": t0, "t1": t1, "ok": True}

    def counters(self) -> dict:
        return {"retries": self.engine.compiler.stats["retries"]}

    def answers(self) -> List[Dict[str, np.ndarray]]:
        self.kept[self.last[0]] = self.last[1]
        got = [drive.host_edges(g) for g in self.kept.values()]
        self.kept.clear()
        del self.engine, self.last
        return got


def _mismatch(got: Dict[str, np.ndarray], bags) -> int:
    total = 0
    for label, (s, d) in bags.items():
        want = reference.pair_keys(s, d)
        have = got.get(label)
        total += len(want) if have is None else reference.bag_mismatch(
            have, want)
    total += sum(len(v) for k, v in got.items() if k not in bags)
    return total


def compare(run, params: dict, answers) -> drive.Checks:
    bags = reference.edge_bags(run.tables, run.config["graph"])
    return {"edge_mismatch": (max(_mismatch(g, bags) for g in answers), 0)}


def control_answers(run, params: dict, id_dtype, count: int = 2):
    bags = reference.edge_bags(run.tables, run.config["graph"], id_dtype)
    got = {label: reference.pair_keys(s, d) for label, (s, d) in
           bags.items()}
    return [got] * count
