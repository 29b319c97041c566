"""Pallas TPU kernel: blocked push-style SpMV over COO edges.

Computes ``y[v] = sum over valid edges (u -> v) of x[u]`` — the inner loop
of push PageRank (and any edge-weighted aggregation once the caller folds
the weight into ``x``).  TPU adaptation: the scatter-add over destination
vertices is lowered as a **one-hot matmul** — each edge tile brings its
source values ``x[src]`` (gathered in XLA before the call, see
``_coo_tiling``), builds a one-hot (TILE x SEG_BLOCK) destination matrix
scaled by those values, and contracts it with a ones-vector on the MXU,
accumulating over grid steps into the output block (the same idiom as
``segment_csr``, generalized from counts to weighted sums).

Grid = (vertices/SEG_BLOCK, edges/TILE); the output block for a given
vertex tile is revisited across all edge tiles (accumulate pattern).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._coo_tiling import pad_coo

TILE = 1024
SEG_BLOCK = 1024


def _spmv_kernel(dst_ref, vals_ref, out_ref):
    seg_tile = pl.program_id(0)
    inp_tile = pl.program_id(1)

    @pl.when(inp_tile == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    local = dst_ref[...] - seg_tile * SEG_BLOCK
    # scaled one-hot contraction on the MXU:
    #   (1, TILE) x (TILE, SEG_BLOCK) -> (SEG_BLOCK,)
    # HIGHEST keeps the f32 values exact; the default precision rounds
    # them to bf16 on the MXU
    onehot = (
        local[:, None] == jnp.arange(SEG_BLOCK, dtype=jnp.int32)[None, :]
    ).astype(jnp.float32) * vals_ref[...][:, None]
    out_ref[...] += jnp.dot(
        jnp.ones((1, onehot.shape[0]), jnp.float32), onehot,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )[0]


@functools.partial(jax.jit, static_argnames=("num_vertices", "interpret"))
def edge_spmv(src: jax.Array, dst: jax.Array, valid: jax.Array,
              x: jax.Array, num_vertices: int,
              interpret: bool = True) -> jax.Array:
    """``y[v] = sum_{valid (u,v)} x[u]`` over COO edge arrays.

    ``interpret=True`` runs the kernel body in Python on the host (the CPU
    backend); on TPU pass ``interpret=False``.
    """
    dst_p, vals_p, grid, s_pad = pad_coo(
        src, dst, valid, x.astype(jnp.float32), num_vertices, TILE,
        SEG_BLOCK, fill=0.0)
    out = pl.pallas_call(
        _spmv_kernel,
        name="edge_spmv",
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE,), lambda s, i: (i,)),
            pl.BlockSpec((TILE,), lambda s, i: (i,)),
        ],
        out_specs=pl.BlockSpec((SEG_BLOCK,), lambda s, i: (s,)),
        out_shape=jax.ShapeDtypeStruct((s_pad,), jnp.float32),
        interpret=interpret,
    )(dst_p, vals_p)
    return out[:num_vertices]
