"""Distributed joins: hash-partitioned shard_map execution.

The paper's single-box assumption is the piece that does not scale; the
standard distributed adaptation is: hash-partition both tables on the join
key across the ``data`` mesh axis (one all_to_all each), then run the
shard-local sort-merge join.  JS-MV composes with this naturally — a
materialized view is stored already partitioned by its key, so every reuse
skips the repartition (the distributed version of "materialize once").

Row routing: dest shard = key % n_shards; per-destination capacity is
static (2x fair share by default); :func:`repartition_by_key` returns the
exact count of rows it dropped, for the caller to check.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from repro.relational.join import sort_merge_join
from repro.relational.table import Table


def _route_local(tbl_cols: Dict[str, jax.Array], valid: jax.Array,
                 key: jax.Array, n: int, cap: int):
    """Scatter local rows into (n, cap, ...) per-destination buffers."""
    dest = jnp.where(valid, key % n, n)             # invalid -> dropped
    order = jnp.argsort(dest, stable=True)
    sdest = dest[order]
    starts = jnp.searchsorted(sdest, jnp.arange(n, dtype=sdest.dtype))
    rank = jnp.arange(dest.shape[0], dtype=jnp.int32) \
        - starts[jnp.clip(sdest, 0, n - 1)].astype(jnp.int32)
    keep = (sdest < n) & (rank < cap)
    slot = jnp.where(keep, sdest.astype(jnp.int32) * cap + rank, n * cap)
    out_cols = {}
    for name, col in tbl_cols.items():
        buf = jnp.zeros((n * cap,), col.dtype).at[slot].set(
            col[order], mode="drop")
        out_cols[name] = buf.reshape(n, cap)
    vbuf = jnp.zeros((n * cap,), bool).at[slot].set(
        keep & valid[order], mode="drop")
    overflow = jnp.sum((sdest < n) & (rank >= cap) & valid[order])
    return out_cols, vbuf.reshape(n, cap), overflow


def repartition_by_key(table: Table, key_col: str, mesh, axis: str = "data",
                       cap_factor: float = 2.0):
    """Hash-partition a row-sharded Table by ``key_col`` over ``axis``.

    Returns (table, overflow_count): the result rows live on the shard
    owning ``key % n``; overflow_count is the number of dropped rows
    (0 unless a shard received > cap_factor x fair share).
    """
    n = mesh.shape[axis]
    local_rows = table.capacity // n
    cap = max(8, int(cap_factor * local_rows / n + 7) // 8 * 8)

    def body(cols, valid):
        cols = {k: v[0] for k, v in cols.items()}   # strip leading shard dim
        valid = valid[0]
        bufs, vbuf, overflow = _route_local(cols, valid, cols[key_col], n,
                                            cap)
        swapped = {k: jax.lax.all_to_all(v, axis, 0, 0)
                   for k, v in bufs.items()}
        vsw = jax.lax.all_to_all(vbuf, axis, 0, 0)
        out_cols = {k: v.reshape(n * cap)[None] for k, v in swapped.items()}
        return out_cols, vsw.reshape(n * cap)[None], \
            jax.lax.psum(overflow, axis)[None]

    # present the table as (shards, local_rows) blocks
    cols2d = {k: v.reshape(n, local_rows) for k, v in table.columns.items()}
    valid2d = table.valid.reshape(n, local_rows)
    specs_in = ({k: PS(axis, None) for k in cols2d}, PS(axis, None))
    specs_out = ({k: PS(axis, None) for k in cols2d}, PS(axis, None),
                 PS(axis))
    fn = jax.shard_map(body, mesh=mesh, in_specs=specs_in,
                       out_specs=specs_out, check_vma=False)
    out_cols, out_valid, overflow = fn(cols2d, valid2d)
    out = Table(columns={k: v.reshape(-1) for k, v in out_cols.items()},
                valid=out_valid.reshape(-1))
    return out, jnp.max(overflow)


def distributed_join(
    left: Table, right: Table, on: Sequence[Tuple[str, str]], mesh,
    axis: str = "data", capacity_per_shard: int = 1 << 14,
    left_partitioned: bool = False, right_partitioned: bool = False,
):
    """Partitioned equi-join: repartition both sides, join shard-locally.

    ``*_partitioned=True`` skips the exchange for inputs already hash-
    partitioned on their key (JS-MV views are stored this way — reuse is
    collective-free).
    """
    lcol, rcol = on[0]
    n = mesh.shape[axis]
    if not left_partitioned:
        left, _ = repartition_by_key(left, lcol, mesh, axis)
    if not right_partitioned:
        right, _ = repartition_by_key(right, rcol, mesh, axis)

    lrows = left.capacity // n
    rrows = right.capacity // n

    def body(lc, lv, rc, rv):
        lt = Table(columns={k: v.reshape(-1) for k, v in lc.items()},
                   valid=lv.reshape(-1))
        rt = Table(columns={k: v.reshape(-1) for k, v in rc.items()},
                   valid=rv.reshape(-1))
        out = sort_merge_join(lt, rt, on=list(on),
                              capacity=capacity_per_shard)
        return ({k: v[None] for k, v in out.columns.items()},
                out.valid[None])

    lcols = {k: v.reshape(n, lrows) for k, v in left.columns.items()}
    rcols = {k: v.reshape(n, rrows) for k, v in right.columns.items()}
    specs_in = ({k: PS(axis, None) for k in lcols}, PS(axis, None),
                {k: PS(axis, None) for k in rcols}, PS(axis, None))
    out_cols_spec = {k: PS(axis, None)
                     for k in list(lcols) + list(rcols)}
    fn = jax.shard_map(body, mesh=mesh, in_specs=specs_in,
                       out_specs=(out_cols_spec, PS(axis, None)),
                       check_vma=False)
    out_cols, out_valid = fn(lcols, left.valid.reshape(n, lrows),
                             rcols, right.valid.reshape(n, rrows))
    return Table(columns={k: v.reshape(-1) for k, v in out_cols.items()},
                 valid=out_valid.reshape(-1))
