"""Share of the ``estimate``-sized join steps' allotted slots that hold a
row, in %: the registry counter ``pipeline_estimate_rows_total``, ``used``
over ``allotted``, summed over every kept attempt of the process (each
extract of a run sizes the same steps alike, so set-up does not move the
ratio).  Steps bound by their probe side are not in it.

Nothing where the program has no such counter, or allotted no slot."""

METRIC = "pipeline_estimate_rows_total"


def read(run):
    from repro import obs

    if obs.REGISTRY.get(METRIC) is None:
        return None
    used = obs.REGISTRY.value(METRIC, rows="used")
    allotted = obs.REGISTRY.value(METRIC, rows="allotted")
    if not allotted:
        return None
    run.notes.append(f"estimate-sized join-step slots: {used:.0f} of "
                     f"{allotted:.0f} allotted hold a row")
    return 100.0 * used / allotted
