"""Share of the join steps' allotted slots that hold a row, in %: the
registry counter ``pipeline_capacity_rows_total``, ``used`` over
``allotted``, summed over every kept attempt of the process (every extract
of a run sizes the same unit alike, so set-up does not move the ratio).

Nothing where the program has no such counter, or allotted no slot."""

METRIC = "pipeline_capacity_rows_total"


def read(run):
    from repro import obs

    if obs.REGISTRY.get(METRIC) is None:
        return None
    used = obs.REGISTRY.value(METRIC, rows="used")
    allotted = obs.REGISTRY.value(METRIC, rows="allotted")
    if not allotted:
        return None
    run.notes.append(f"join-step slots: {used:.0f} of {allotted:.0f} "
                     f"allotted hold a row")
    return 100.0 * used / allotted
