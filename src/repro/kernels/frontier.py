"""Pallas TPU kernel: frontier expansion (BFS / k-hop inner loop).

Computes ``next[v] = (exists valid edge (u -> v) with frontier[u]) and not
visited[v]`` — one BFS level.  TPU adaptation: reached-neighbor counting is
the same one-hot matmul as ``spmv`` (frontier membership as a 0/1 value
per edge, gathered in XLA before the call, contracted with the one-hot
destination matrix on the MXU); the ``~visited`` filter is applied once,
on the last edge tile, after the counts for this vertex block have fully
accumulated.

Grid = (vertices/SEG_BLOCK, edges/TILE), accumulate pattern with a
finalization step — out holds raw reach-counts until the last input tile
converts them to the 0/1 next-frontier mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._coo_tiling import pad_coo

TILE = 1024
SEG_BLOCK = 1024


def _frontier_kernel(dst_ref, front_ref, visited_ref, out_ref):
    seg_tile = pl.program_id(0)
    inp_tile = pl.program_id(1)

    @pl.when(inp_tile == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    local = dst_ref[...] - seg_tile * SEG_BLOCK
    onehot = (
        local[:, None] == jnp.arange(SEG_BLOCK, dtype=jnp.int32)[None, :]
    ).astype(jnp.float32) * front_ref[...][:, None]
    out_ref[...] += jnp.dot(
        jnp.ones((1, onehot.shape[0]), jnp.float32), onehot,
        preferred_element_type=jnp.float32,
    )[0].astype(jnp.int32)

    @pl.when(inp_tile == pl.num_programs(1) - 1)
    def _finalize():
        reached = out_ref[...] > 0
        out_ref[...] = (reached & ~visited_ref[...]).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_vertices", "interpret"))
def frontier_expand(src: jax.Array, dst: jax.Array, valid: jax.Array,
                    frontier: jax.Array, visited: jax.Array,
                    num_vertices: int, interpret: bool = True) -> jax.Array:
    """One BFS level: bool mask of newly reached vertices.

    ``interpret=True`` runs the kernel body in Python on the host (the CPU
    backend); on TPU pass ``interpret=False``.
    """
    dst_p, front_p, grid, s_pad = pad_coo(
        src, dst, valid, frontier.astype(jnp.float32), num_vertices, TILE,
        SEG_BLOCK, fill=0.0)
    vis = jnp.pad(visited.astype(bool), (0, s_pad - num_vertices),
                  constant_values=True)
    out = pl.pallas_call(
        _frontier_kernel,
        name="frontier_expand",
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE,), lambda s, i: (i,)),
            pl.BlockSpec((TILE,), lambda s, i: (i,)),
            pl.BlockSpec((SEG_BLOCK,), lambda s, i: (s,)),
        ],
        out_specs=pl.BlockSpec((SEG_BLOCK,), lambda s, i: (s,)),
        out_shape=jax.ShapeDtypeStruct((s_pad,), jnp.int32),
        interpret=interpret,
    )(dst_p, front_p, vis)
    return out[:num_vertices] > 0
