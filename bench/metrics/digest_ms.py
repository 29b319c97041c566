"""Host graph digest per operation: the ``repro.obs`` span ``graph.digest``
(the host sort and hash of the graph's valid rows, inside ``csr``), in ms.
Nothing where the program has no such span."""


def read(run):
    return run.span_ms("graph.digest")
