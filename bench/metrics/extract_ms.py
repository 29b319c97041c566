"""Extraction time per operation: the ``repro.obs`` span
``engine.extract`` (planning, the plan's executables, vertex tables), in
ms.  A served request that re-extracts pays it before its algorithm."""


def read(run):
    return run.span_ms("engine.extract")
