"""Share of the join steps' probe rows that a range table probed, in %:
the registry counter ``pipeline_probe_rows_total``, ``range`` over
``range`` + ``search``, summed over every kept attempt of the process
(every extract of a run probes the same steps alike, so set-up does not
move the ratio).

Nothing where the program has no such counter, or probed no row."""

METRIC = "pipeline_probe_rows_total"


def read(run):
    from repro import obs

    if obs.REGISTRY.get(METRIC) is None:
        return None
    ranged = obs.REGISTRY.value(METRIC, method="range")
    searched = obs.REGISTRY.value(METRIC, method="search")
    if not ranged + searched:
        return None
    run.notes.append(f"join probes: {ranged:.0f} of {ranged + searched:.0f} "
                     f"probe rows through a range table")
    return 100.0 * ranged / (ranged + searched)
