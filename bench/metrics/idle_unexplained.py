"""Share of the traced window in which the device is idle and the host is
not doing the program's own work, in %.

An instant counts when no op runs on the device and the innermost
``repro.obs`` span open on the host (the shortest, as the breakdown's idle
gaps are named) ends in ``.wait`` (the host only blocks on the device) or
there is none (the host is outside the program).  Averaged over the
devices, as ``device_idle`` is.  Nothing without a device plane, or where
the program states no such naming rule (``repro.obs.trace.WAIT_SUFFIX``).
"""
from typing import List, Optional, Sequence, Tuple

from bench import trace

Interval = Tuple[float, float]


def _unexplained(spans: Sequence[trace.Event], window: Interval,
                 suffix: str) -> List[Interval]:
    """Intervals of ``window`` whose innermost span ends in ``suffix`` or
    that no span covers."""
    lo, hi = window
    cuts = sorted({lo, hi} | {x for s in spans for x in (s.start_ns, s.end_ns)
                              if lo < x < hi})
    out: List[Interval] = []
    for a, b in zip(cuts, cuts[1:]):
        open_spans = [s for s in spans if s.start_ns <= a and s.end_ns >= b]
        inner = min(open_spans, key=lambda s: s.dur_ns, default=None)
        if inner is None or inner.name.endswith(suffix):
            if out and out[-1][1] == a:
                out[-1] = (out[-1][0], b)
            else:
                out.append((a, b))
    return out


def _idle(ops: Sequence[trace.Event], window: Interval) -> List[Interval]:
    lo, hi = window
    edges = [lo] + [x for s, e in trace.union(trace.clip(ops, window))
                    for x in (s, e)] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def overlap_ns(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Total length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def share(t: trace.Trace, suffix: str = ".wait") -> Optional[float]:
    width = t.window[1] - t.window[0]
    if not t.devices or width <= 0:
        return None
    program = [h for h in t.host if not h.name.startswith("bench.")]
    unexplained = _unexplained(program, t.window, suffix)
    per = [overlap_ns(_idle(ops, t.window), unexplained)
           for ops in t.devices.values()]
    return 100.0 * sum(per) / len(per) / width


def read(run):
    from repro.obs import trace as spans

    suffix = getattr(spans, "WAIT_SUFFIX", None)
    if run.trace is None or suffix is None:
        return None
    return share(run.trace, suffix)
