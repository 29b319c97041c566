"""A kernel's share of its roofline, from the trace and its cost function.

``bench/roofline/<kernel>.py`` names the kernel's events (``NAMES``) and
gives ``cost(event, run) -> (ops, bytes)``: what the algorithm needs for
that call, from shapes, whatever implements it.  The least time of a call
is the larger of ops over the peak op rate and bytes over the peak HBM
bandwidth (``bench/peaks.json``); the share is the least time of all calls
over their device time.  A kernel with no event in the window reads
nothing (``None``), never 0.
"""
from __future__ import annotations

from typing import Optional

from bench import trace


def share(run, kernel: str) -> Optional[float]:
    if run.trace is None:
        return None
    mod = run.roofline(kernel)
    events = trace.kernel_events(run.trace, mod.NAMES)
    if not events:
        return None
    peaks = run.peaks()
    least = {"compute": 0.0, "memory": 0.0}
    spent = 0.0
    for e in events:
        ops, nbytes = mod.cost(e, run)
        t_ops = ops / peaks[mod.OPS_PEAK]
        t_mem = nbytes / peaks["hbm_bytes_per_s"]
        least["compute" if t_ops > t_mem else "memory"] += max(t_ops, t_mem)
        spent += e.dur_ns * 1e-9
    bound = max(least, key=least.get)
    run.notes.append(f"{kernel}: {len(events)} calls, {spent:.6f} s on "
                     f"the device, least {sum(least.values()):.9f} s, "
                     f"{bound}-bound")
    return 100.0 * sum(least.values()) / spent
