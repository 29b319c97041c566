"""Overflow retries of the pipeline inside the window, per operation:
the change in ``PipelineCompiler.stats["retries"]``."""


def read(run):
    return run.per_op(run.counters_after["retries"]
                      - run.counters_before["retries"])
