"""CSR lookup or build per operation: the ``repro.obs`` span ``csr``
(the graph's content digest, then the cached or rebuilt CSR), in ms."""


def read(run):
    return run.span_ms("csr")
