"""Share of the traced window in which no op ran on the device, in %:
1 - (union of device op intervals / window), from the profiler trace."""
from bench import trace


def read(run):
    if run.trace is None:
        return None
    idle = trace.idle_share(run.trace)
    return None if idle is None else idle * 100.0
