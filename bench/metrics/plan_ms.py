"""Planner time per operation: the ``repro.obs`` span ``plan``, in ms."""


def read(run):
    return run.span_ms("plan")
