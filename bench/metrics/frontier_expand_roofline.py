"""``frontier_expand``'s share of its roofline over the window, in %."""
from bench import rooflines


def read(run):
    return rooflines.share(run, "frontier_expand")
