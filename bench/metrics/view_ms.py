"""JS-MV view materialization per operation: the ``repro.obs`` span
``view.build`` (opened only where a view is built, never on reuse), in
ms.  Nothing where no view was built or the program has no such span."""


def read(run):
    return run.span_ms("view.build")
