"""Reduce a profiler trace of the window to device and host numbers.

A traced run writes one ``.xplane.pb`` (``jax.profiler``).  From it:

* device ops: the events of each TPU plane's ``XLA Ops`` line;
* host spans: the harness's own ``jax.profiler.TraceAnnotation`` spans
  (``bench.*``), plus the program's ``repro.obs`` spans moved onto the
  trace's clock through the ``bench.window`` annotation, whose start is
  known on both clocks.

Reductions, all on plain lists so that tests can feed them made-up
events: the union of device-busy intervals inside the window, the idle
share, kernel time by name, the top device ops, and each idle gap
labelled by the innermost host span open at its midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float
    stats: Tuple[Tuple[str, object], ...] = ()

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns

    def stat(self, key: str, default=None):
        for k, v in self.stats:
            if k == key:
                return v
        return default


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]     # plane name -> its op events
    host: List[Event]                   # host spans, trace clock
    window: Tuple[float, float]         # ns, trace clock

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def load(trace_dir: str, obs_spans: Sequence[dict] = (),
         window_start_perf: Optional[float] = None) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops: List[Event] = []
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    ops.extend(Event(e.name, e.start_ns,
                                     e.start_ns + e.duration_ns,
                                     tuple(e.stats)) for e in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith("bench."))
    windows = [e for e in host if e.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    window = (windows[0].start_ns, windows[0].end_ns)
    if window_start_perf is not None:
        offset = window[0] - window_start_perf * 1e9
        host.extend(Event(s["name"], s["start_s"] * 1e9 + offset,
                          (s["start_s"] + s["dur_s"]) * 1e9 + offset)
                    for s in obs_spans)
    return Trace(devices=devices, host=host, window=window)


def clip(events: Iterable[Event], window: Tuple[float, float]
         ) -> List[Tuple[float, float]]:
    lo, hi = window
    return [(max(e.start_ns, lo), min(e.end_ns, hi)) for e in events
            if e.end_ns > lo and e.start_ns < hi]


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def busy_s(trace: Trace) -> Optional[float]:
    """Seconds of the window some op ran, averaged over the devices."""
    if not trace.devices:
        return None
    per = [sum(e - s for s, e in union(clip(ops, trace.window)))
           for ops in trace.devices.values()]
    return sum(per) / len(per) * 1e-9


def idle_share(trace: Trace) -> Optional[float]:
    busy = busy_s(trace)
    if busy is None or trace.window_s <= 0:
        return None
    return 1.0 - busy / trace.window_s


def op_name(text: str) -> str:
    """The HLO instruction name of a device op event.

    TPU op events are named by their HLO text,
    ``%bloom_probe.3 = s32[2881536]{...} custom-call(...)``; this returns
    ``bloom_probe.3``.
    """
    return text.split(" = ", 1)[0].strip().lstrip("%")


def base_name(text: str) -> str:
    """``op_name`` without its numeric suffix: ``bloom_probe``."""
    name = op_name(text)
    stem, _, suffix = name.rpartition(".")
    return stem if stem and suffix.isdigit() else name


def kernel_events(trace: Trace, names: Sequence[str]) -> List[Event]:
    """Ops starting inside the window whose own name (not an operand's)
    is one of ``names``."""
    lo, hi = trace.window
    return [e for ops in trace.devices.values() for e in ops
            if lo <= e.start_ns < hi and base_name(e.name) in names]


def top_ops(trace: Trace, n: int = 10) -> List[List[object]]:
    """The ``n`` ops (by HLO instruction name) that took the most device
    seconds."""
    total: Dict[str, float] = {}
    for ops in trace.devices.values():
        for s, e, name in ((max(o.start_ns, trace.window[0]),
                            min(o.end_ns, trace.window[1]), op_name(o.name))
                           for o in ops):
            if e > s:
                total[name] = total.get(name, 0.0) + (e - s) * 1e-9
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs] for name, secs in ranked]


def _label(host: Sequence[Event], t: float) -> str:
    open_spans = [h for h in host if h.start_ns <= t < h.end_ns
                  and h.name != WINDOW_SPAN]
    if not open_spans:
        return "(no span)"
    return min(open_spans, key=lambda h: h.dur_ns).name


def idle_gaps(trace: Trace, n: int = 10) -> List[List[object]]:
    """The ``n`` longest idle gaps of the first device inside the window,
    each named by the innermost host span open at its midpoint."""
    if not trace.devices:
        return []
    ops = next(iter(trace.devices.values()))
    busy = union(clip(ops, trace.window))
    lo, hi = trace.window
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_label(trace.host, (s + e) / 2), (e - s) * 1e-9]
            for s, e in gaps[:n]]
