"""A new session's extract, back to back: the paper's graph extraction
time (plan, views, joins) on loaded tables.

Each step opens an ``ExtractionEngine`` over the run's database with the
process's one ``PipelineCompiler`` and extracts the whole graph model:
Algorithm 2 searches again and every JS-MV view is built again, while the
executables stay warm.  A step that took its plan or a view from an
earlier session has not done a new session's work and counts as failed.
The previous session's engine and graph are dropped before the next
opens, as a closed session's are; the sampled answer is kept, on the
device, as the warm extract keeps it.  Answers, and their comparison, are
the warm extract's (``bench/operations/extract.py``)."""
from __future__ import annotations

import time

from bench import drive, spec

_extract = spec.operation("extract")
compare = _extract.compare
control_answers = _extract.control_answers


class Operation(_extract.Operation):
    def _session(self):
        from repro.api import ExtractionEngine

        self.engine = ExtractionEngine(self.run.db, compiler=self.compiler)
        return self.engine.extract(self.run.model)

    def setup(self) -> None:
        from repro.core.pipeline import PipelineCompiler

        self.compiler = PipelineCompiler()
        self._session()
        self._session()

    def step(self) -> dict:
        self.engine = self.last = None
        t0 = time.perf_counter()
        with drive.annotate("bench.session"):
            res = self._session()
        t1 = time.perf_counter()
        if self.n == self.keep_index:
            self.kept[self.n] = res.graph
        self.last = (self.n, res.graph)
        self.n += 1
        reused = res.provenance.plan_cache_hit or res.provenance.views_reused
        return {"t0": t0, "t1": t1, "ok": not reused}
