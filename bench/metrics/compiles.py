"""Backend compiles inside the window, per operation: there should be
none once set-up has warmed every shape."""


def read(run):
    return run.per_op(run.compiles)
