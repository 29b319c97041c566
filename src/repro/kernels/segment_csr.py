"""Pallas TPU kernel: masked histogram (CSR row-count build).

TPU adaptation: scatter-add is not a native TPU primitive; the idiomatic
lowering is a **one-hot matmul** — each input tile becomes a one-hot matrix
(TILE x SEG_BLOCK) contracted with a ones-vector on the MXU, accumulated
over grid steps into the output block.  The segment dimension is tiled too,
so arbitrary vertex counts stream through VMEM-sized blocks.

Grid = (segments/SEG_BLOCK, inputs/TILE); the output block for a given
segment tile is revisited across all input tiles (accumulate pattern).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE = 2048
SEG_BLOCK = 2048


def _hist_kernel(vals_ref, valid_ref, out_ref):
    seg_tile = pl.program_id(0)
    inp_tile = pl.program_id(1)

    @pl.when(inp_tile == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    vals = vals_ref[...]
    valid = valid_ref[...]
    base = seg_tile * SEG_BLOCK
    local = vals - base
    # fold the mask into the index (-1 hits no column): Mosaic cannot
    # broadcast a 1-D bool vector to a column
    local = jnp.where(valid, local, -1)
    # one-hot contraction on the MXU: (TILE, SEG_BLOCK) x (TILE,) -> SEG_BLOCK
    onehot = (
        local[:, None] == jnp.arange(SEG_BLOCK, dtype=jnp.int32)[None, :]
    ).astype(jnp.float32)
    out_ref[...] += jnp.dot(
        jnp.ones((1, onehot.shape[0]), jnp.float32), onehot,
        preferred_element_type=jnp.float32,
    )[0].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def segment_counts(values: jax.Array, valid: jax.Array, num_segments: int,
                   interpret: bool = True) -> jax.Array:
    n = values.shape[0]
    n_pad = ((n + TILE - 1) // TILE) * TILE
    s_pad = ((num_segments + SEG_BLOCK - 1) // SEG_BLOCK) * SEG_BLOCK
    vals = jnp.pad(values.astype(jnp.int32), (0, n_pad - n),
                   constant_values=-1)
    vmask = jnp.pad(valid, (0, n_pad - n), constant_values=False)
    grid = (s_pad // SEG_BLOCK, n_pad // TILE)
    out = pl.pallas_call(
        _hist_kernel,
        name="segment_counts",
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE,), lambda s, i: (i,)),
            pl.BlockSpec((TILE,), lambda s, i: (i,)),
        ],
        out_specs=pl.BlockSpec((SEG_BLOCK,), lambda s, i: (s,)),
        out_shape=jax.ShapeDtypeStruct((s_pad,), jnp.int32),
        interpret=interpret,
    )(vals, vmask)
    return out[:num_segments]
