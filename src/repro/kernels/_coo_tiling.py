"""Shared tiling prologue for the COO edge kernels (spmv / label_prop /
frontier): mask and pad edge arrays to a whole number of TILE-sized input
blocks, round the vertex axis up to SEG_BLOCK, and derive the 2-D
accumulate grid.

The per-edge source values (``x[src]``) are gathered here, in XLA, before
the ``pallas_call``: Mosaic lowers no 1-D gather inside a kernel.  Invalid
and padding edges get ``dst = -1``, which lands in no segment block, so the
kernels need no separate mask.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def pad_coo(src: jax.Array, dst: jax.Array, valid: jax.Array,
            x: jax.Array, num_vertices: int, tile: int, seg_block: int,
            fill) -> Tuple[jax.Array, jax.Array, Tuple[int, int], int]:
    """Returns ``(dst, vals, grid, s_pad)`` ready for ``pallas_call``.

    ``vals[e] = x[src[e]]`` for valid edges and ``fill`` elsewhere.
    """
    n = src.shape[0]
    n_pad = ((n + tile - 1) // tile) * tile
    s_pad = ((num_vertices + seg_block - 1) // seg_block) * seg_block
    src = jnp.clip(src.astype(jnp.int32), 0, x.shape[0] - 1)
    vals = jnp.where(valid, x[src], jnp.asarray(fill, x.dtype))
    dst = jnp.where(valid, dst.astype(jnp.int32), -1)
    dst_p = jnp.pad(dst, (0, n_pad - n), constant_values=-1)
    vals_p = jnp.pad(vals, (0, n_pad - n), constant_values=fill)
    grid = (s_pad // seg_block, n_pad // tile)
    return dst_p, vals_p, grid, s_pad
