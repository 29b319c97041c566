"""Share of the Bloom prefilter's probe rows that pass it, in %: the
registry counter ``pipeline_bloom_rows_total``, ``passed`` over
``probed``, over the whole process (every extract of a run filters the
same rows, so set-up does not move the ratio).

Where the program exports the counter and nothing was probed, no
prefilter ran (the pipeline prefilters on the TPU only), so every probe
row went on to the probe: 100.  Nothing where the program has no such
counter."""

METRIC = "pipeline_bloom_rows_total"


def read(run):
    from repro import obs

    if obs.REGISTRY.get(METRIC) is None:
        return None
    probed = obs.REGISTRY.value(METRIC, outcome="probed")
    passed = obs.REGISTRY.value(METRIC, outcome="passed")
    run.notes.append(f"bloom prefilter: {passed:.0f} of {probed:.0f} probe "
                     f"rows passed")
    return 100.0 * passed / probed if probed else 100.0
