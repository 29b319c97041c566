"""Device-memory accounting: byte sizes from metadata, HBM watermarks.

Two complementary views of "what does the engine hold on the device":

* **Bottom-up** — :func:`entry_nbytes` sizes a cached object (Table,
  ExtractedGraph, CSRGraph, cached view/extraction wrappers) purely from
  array ``shape``/``dtype`` metadata, so accounting never forces a device
  transfer or materializes a buffer.  The engine's ``_LRUCache``s use it
  to maintain per-cache resident-byte totals (``engine_cache_bytes``
  gauges) and, optionally, byte-budget eviction.
* **Top-down** — :func:`device_memory_stats` samples the runtime's own
  live/peak/limit counters (``jax`` ``device.memory_stats()``, present on
  TPU/GPU backends; absent on CPU where the function degrades to ``{}``)
  into ``device_memory_bytes{device,kind}`` gauges.

Sizing is duck-typed on structural attributes rather than importing the
relational/graph layers: ``obs`` sits at the bottom of the dependency
stack and must not import upward.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from repro.obs.metrics import REGISTRY

__all__ = ["array_nbytes", "table_nbytes", "graph_nbytes", "csr_nbytes",
           "entry_nbytes", "device_memory_stats"]


def array_nbytes(a) -> int:
    """Byte size of one array from shape x dtype metadata (no transfer)."""
    shape = getattr(a, "shape", None)
    dtype = getattr(a, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for dim in shape:
        n *= int(dim)
    try:
        return n * int(np.dtype(dtype).itemsize)
    except TypeError:
        return 0


def table_nbytes(t) -> int:
    """A relational ``Table``: every column plus the validity mask."""
    total = sum(array_nbytes(c) for c in t.columns.values())
    return total + array_nbytes(t.valid)


def csr_nbytes(csr) -> int:
    """A ``CSRGraph``: vertex ids plus per-label offset/target/source."""
    total = array_nbytes(getattr(csr, "vertex_ids", None))
    for field in ("offsets", "targets", "sources"):
        arrays = getattr(csr, field, None) or {}
        total += sum(array_nbytes(a) for a in arrays.values())
    return total


def graph_nbytes(g) -> int:
    """An ``ExtractedGraph``: vertex tables + edge tables."""
    total = 0
    for field in ("vertices", "edges"):
        tables = getattr(g, field, None) or {}
        for t in tables.values():
            total += table_nbytes(t)
    return total


def entry_nbytes(value) -> int:
    """Device-resident bytes of one engine cache entry (duck-typed).

    Host-only entries (plans, profiles, discovery results) size to 0 —
    the gauges account for *device buffers*, not Python objects.  Cached
    views count only the materialized view table; their ``base_tables``
    are shared references into the database snapshot, and counting them
    would double-bill every view against the same buffers.
    """
    if value is None:
        return 0
    if hasattr(value, "columns") and hasattr(value, "valid"):
        return table_nbytes(value)                      # Table
    if hasattr(value, "offsets") and hasattr(value, "vertex_ids"):
        return csr_nbytes(value)                        # CSRGraph
    if hasattr(value, "vertices") and hasattr(value, "edges"):
        return graph_nbytes(value)                      # ExtractedGraph
    if hasattr(value, "pattern") and hasattr(value, "table"):
        return entry_nbytes(value.table)                # _CachedView
    if hasattr(value, "graph") and hasattr(value, "plan"):
        return entry_nbytes(value.graph)                # _CachedExtraction
    return 0


def device_memory_stats(gauges: bool = True) -> Dict[str, Dict[str, int]]:
    """Live/peak/limit HBM bytes per device, mirrored into gauges.

    Returns ``{device: {"in_use": n, "peak": n, "limit": n}}`` with only
    the kinds the backend reports.  CPU backends report no
    ``memory_stats`` (``None``) — the result is ``{}`` and nothing is
    gauged, so the call is safe to make unconditionally from
    ``cache_info()``.  A backend that fails to report raises.
    """
    import jax

    out: Dict[str, Dict[str, int]] = {}
    for d in jax.local_devices():
        stats = d.memory_stats()
        if not stats:
            continue
        rec: Dict[str, int] = {}
        for key, kind in (("bytes_in_use", "in_use"),
                          ("peak_bytes_in_use", "peak"),
                          ("bytes_limit", "limit")):
            if key in stats:
                rec[kind] = int(stats[key])
        if not rec:
            continue
        name = str(d)
        out[name] = rec
        if gauges:
            for kind, v in rec.items():
                REGISTRY.gauge(
                    "device_memory_bytes",
                    help="Device allocator watermarks (live/peak/limit).",
                    device=name, kind=kind).set(float(v))
    return out
