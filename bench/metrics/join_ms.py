"""Device time per operation of the ops that run the plan's joins, in ms:
the union of the device intervals of ops under a ``join:<alias>`` or
``outer:<branch>`` scope (a join step with its Bloom prefilter and probe,
an outer-join attachment), inside the traced window.

A TPU profile names each op by its HLO instruction and places it in a
module (the ``XLA Modules`` line); the program maps (module, instruction)
to the step's scope (``repro.core.pipeline.op_scopes``).  A union, since a
``while`` op's time holds its body's ops.  Notes the scopes that took the
most device time.  Nothing without a device plane, or where the program
names no step."""
import bisect
import glob
import os
from typing import Dict, List, Sequence, Tuple

from bench import trace

SCOPES = ("join:", "outer:")
MODULE_LINE = "XLA Modules"

Module = Tuple[float, float, str]          # start ns, end ns, module name


def modules_in(trace_dir: str) -> Dict[str, List[Module]]:
    """Each TPU plane's executable runs from the newest ``.xplane.pb``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    out: Dict[str, List[Module]] = {}
    if not paths:
        return out
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/device:TPU:"):
            out[plane.name] = sorted(
                (e.start_ns, e.start_ns + e.duration_ns,
                 e.name.split("(", 1)[0])
                for line in plane.lines if line.name == MODULE_LINE
                for e in line.events)
    return out


def scoped_intervals(ops: Sequence[trace.Event], modules: Sequence[Module],
                     scopes: Dict[str, Dict[str, str]]
                     ) -> Dict[str, List[Tuple[float, float]]]:
    """Device intervals of ``ops`` by the step scope of their (module,
    instruction); an op outside every module run, or unscoped, is left
    out."""
    starts = [m[0] for m in modules]
    out: Dict[str, List[Tuple[float, float]]] = {}
    for op in ops:
        i = bisect.bisect_right(starts, op.start_ns) - 1
        if i < 0 or op.start_ns >= modules[i][1]:
            continue
        scope = scopes.get(modules[i][2], {}).get(trace.op_name(op.name))
        if scope is not None:
            out.setdefault(scope, []).append((op.start_ns, op.end_ns))
    return out


def busy_ns(intervals, window) -> float:
    return sum(e - s for s, e in trace.union(
        (max(s, window[0]), min(e, window[1])) for s, e in intervals
        if e > window[0] and s < window[1]))


def join_ms(t: trace.Trace, modules: Dict[str, List[Module]],
            scopes: Dict[str, Dict[str, str]], operations: int, notes=None):
    per_device = []
    by_scope: Dict[str, float] = {}
    for plane, ops in t.devices.items():
        found = scoped_intervals(ops, modules.get(plane, ()), scopes)
        joins = [iv for scope, ivs in found.items()
                 if scope.startswith(SCOPES) for iv in ivs]
        if joins:
            per_device.append(busy_ns(joins, t.window))
        for scope, ivs in found.items():
            by_scope[scope] = by_scope.get(scope, 0.0) + busy_ns(ivs, t.window)
    if not per_device:
        return None
    if notes is not None:
        top = sorted(by_scope.items(), key=lambda kv: -kv[1])[:6]
        notes.append("device time by step scope: " + ", ".join(
            f"{scope} {ns * 1e-9:.3f} s" for scope, ns in top))
    return sum(per_device) / len(per_device) * 1e-6 / max(1, operations)


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    try:
        from repro.core.pipeline import op_scopes
    except ImportError:
        return None
    from bench import harness

    scopes = op_scopes()
    if not scopes:
        return None
    modules = modules_in(os.path.join(harness.TRACE_ROOT, str(os.getpid())))
    return join_ms(run.trace, modules, scopes, len(run.ops), run.notes)
