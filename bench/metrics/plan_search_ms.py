"""Algorithm 2's plan search per operation: the ``repro.obs`` span
``plan.search`` (opened only on a plan-cache miss), in ms.  Nothing where
every plan came from the cache or the program has no such span."""


def read(run):
    return run.span_ms("plan.search")
