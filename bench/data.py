"""Tables of a configuration, generated from a seed.

A configuration file (``bench/configs/<name>.json``) lists each table with
its row count and, per column, how its values are drawn:

``{"serial": s}``           s, s+1, ... (a key)
``{"uniform": [lo, hi]}``   integers uniform in [lo, hi]
``{"real": [lo, hi]}``      float32 uniform in [lo, hi)
``{"of": "t.c"}``           uniform over the values of column c of table t
``{"run_of": "t.c"}``       consecutive entries of a seeded permutation of
                            the values of t.c, from a uniform start per
                            group: distinct values within a group

A table may have ``"groups": {"size": [lo, hi]}``: its rows come in groups
(a ticket's lines) of a size uniform in [lo, hi], the last one cut to the
row count.  A column with ``"per": "group"`` is drawn once per group and
repeated on its rows.  A column with ``"null": p`` holds the
configuration's ``null_key`` in each row with probability p.

Tables are drawn in file order and columns in file order from one
``numpy.random.Generator`` seeded with the run's seed, so a seed fixes
every value.  ``shrink`` divides every row count (a tiny copy for tests on
the CPU); the benchmark itself always runs at ``shrink=1``.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

Tables = Dict[str, Dict[str, np.ndarray]]


def rows_of(config: dict, table: str, shrink: int = 1) -> int:
    rows = int(config["tables"][table]["rows"])
    return rows if shrink == 1 else max(4, rows // shrink)


def group_sizes(rng: np.random.Generator, size: list, n: int) -> np.ndarray:
    """Sizes uniform in ``size`` whose sum is ``n``, the last one cut."""
    lo, hi = size
    sizes = rng.integers(lo, hi + 1, n // lo + 1)
    count = int(np.searchsorted(np.cumsum(sizes), n)) + 1
    sizes = sizes[:count]
    sizes[-1] -= int(sizes.sum()) - n
    return sizes


def draw_column(rng: np.random.Generator, spec: dict, n: int,
                tables: Tables, line: Optional[np.ndarray] = None
                ) -> np.ndarray:
    """Draw ``n`` values of one column by its spec (see module doc).

    ``line`` is each row's index inside its group (``run_of`` only)."""
    if "serial" in spec:
        return np.arange(spec["serial"], spec["serial"] + n, dtype=np.int32)
    if "uniform" in spec:
        lo, hi = spec["uniform"]
        return rng.integers(lo, hi + 1, n, dtype=np.int64).astype(np.int32)
    if "real" in spec:
        lo, hi = spec["real"]
        return rng.uniform(lo, hi, n).astype(np.float32)
    if "run_of" in spec:
        table, col = spec["run_of"].split(".")
        values = tables[table][col][rng.permutation(len(tables[table][col]))]
        first = np.flatnonzero(line == 0)
        start = np.repeat(rng.integers(0, len(values), len(first)),
                          np.diff(np.append(first, n)))
        return values[(start + line) % len(values)]
    table, col = spec["of"].split(".")
    values = tables[table][col]
    return values[rng.integers(0, len(values), n)]


def make_table(config: dict, name: str, rng: np.random.Generator,
               tables: Tables, shrink: int = 1) -> Dict[str, np.ndarray]:
    spec = config["tables"][name]
    n = rows_of(config, name, shrink)
    sizes = (group_sizes(rng, spec["groups"]["size"], n)
             if "groups" in spec else np.ones(n, np.int64))
    line = np.arange(n) - np.repeat(np.cumsum(sizes) - sizes, sizes)
    out = {}
    for col, cspec in spec["columns"].items():
        if cspec.get("per") == "group":
            values = np.repeat(draw_column(rng, cspec, len(sizes), tables),
                               sizes)
        else:
            values = draw_column(rng, cspec, n, tables, line)
        if "null" in cspec:
            values = np.where(rng.random(n) < cspec["null"],
                              values.dtype.type(config["null_key"]), values)
        out[col] = values
    return out


def make_tables(config: dict, seed: int, shrink: int = 1) -> Tables:
    rng = np.random.default_rng(seed)
    tables: Tables = {}
    for name in config["tables"]:
        tables[name] = make_table(config, name, rng, tables, shrink)
    return tables
