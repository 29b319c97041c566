"""``bloom_probe``'s share of its roofline over the window, in %."""
from bench import rooflines


def read(run):
    return rooflines.share(run, "bloom_probe")
