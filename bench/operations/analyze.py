"""Served ``GraphService.analyze``, one client, closed loop.

Each request draws ``seeds`` distinct vertices of ``seed_label`` from the
run's generator and asks for ``algorithm`` (with ``k``) from them.  Every
answer of the window is compared: the digest of the served distances
against the reference's."""
from __future__ import annotations

import time
from typing import List, Tuple

import numpy as np

from bench import drive, reference


def _numbering(run):
    numbering = reference.dense_numbering(run.tables, run.config["graph"])
    return numbering, sum(len(ids) for _, ids in numbering.values())


def _draw_seeds(run, params: dict, numbering) -> np.ndarray:
    base, ids = numbering[params["seed_label"]]
    pick = run.rng.choice(len(ids), params["seeds"], replace=False)
    return np.sort(base + pick)


class Operation(drive.Operation):
    def __init__(self, run, params: dict):
        super().__init__(run, params)
        self.numbering, _ = _numbering(run)
        self.got: List[Tuple[np.ndarray, str]] = []

    def _request(self, seeds: np.ndarray) -> dict:
        return self.svc.analyze(self.name, algorithm=self.params["algorithm"],
                                seeds=[int(s) for s in seeds],
                                k=self.params["k"])

    def setup(self) -> None:
        from repro.serving import GraphService

        self.name = self.run.model.name
        self.svc = GraphService(self.run.db, {self.name: self.run.model})
        self._request(_draw_seeds(self.run, self.params, self.numbering))

    def step(self) -> dict:
        seeds = _draw_seeds(self.run, self.params, self.numbering)
        t0 = time.perf_counter()
        with drive.annotate("bench.analyze"):
            out = self._request(seeds)
        t1 = time.perf_counter()
        self.got.append((seeds, out["values"].get("digest")))
        return {"t0": t0, "t1": t1, "ok": True}

    def counters(self) -> dict:
        return {"retries": self.svc.compiler.stats["retries"]}

    def answers(self) -> List[Tuple[np.ndarray, str]]:
        self.svc.close()
        del self.svc
        return self.got


def _distances(run, params, seeds_list, id_dtype=np.int32) -> List[str]:
    graph = run.config["graph"]
    numbering, n = _numbering(run)
    src, dst = reference.dense_edges(
        graph, reference.edge_bags(run.tables, graph), numbering, id_dtype)
    return [reference.array_digest(reference.khop(src, dst, seeds, n,
                                                  params["k"]))
            for seeds in seeds_list]


def compare(run, params: dict, answers) -> drive.Checks:
    want = _distances(run, params, [seeds for seeds, _ in answers])
    wrong = sum(got != w for (_, got), w in zip(answers, want))
    return {"khop_answers_wrong": (wrong, 0)}


def control_answers(run, params: dict, id_dtype, count: int = 3):
    numbering, _ = _numbering(run)
    seeds_list = [_draw_seeds(run, params, numbering) for _ in range(count)]
    return list(zip(seeds_list, _distances(run, params, seeds_list,
                                           id_dtype)))
