"""Pallas TPU kernel: one step of min-label propagation (WCC inner loop).

Computes ``new[v] = min(labels[v], min over valid edges (u -> v) of
labels[u])`` — hook an undirected graph up by passing both edge directions
and iterate to a fixed point for weakly connected components.

TPU adaptation: the scatter-min over destination vertices is the masked-min
variant of the one-hot idiom — each edge tile brings its source labels
``labels[src]`` (gathered in XLA before the call, see ``_coo_tiling``),
builds the (TILE x SEG_BLOCK) one-hot destination mask, lifts non-members
to +inf (INT32_MAX), and min-reduces over the edge axis on the VPU,
folding into the output block across grid steps.  The
output block is initialized from the vertex's own label so the identity
``new <= labels`` holds even for isolated vertices.

Grid = (vertices/SEG_BLOCK, edges/TILE), accumulate (min) pattern.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._coo_tiling import pad_coo

TILE = 1024
SEG_BLOCK = 1024

_INT_MAX = 2**31 - 1  # python int: jnp scalars would be captured as consts


def _minlabel_kernel(dst_ref, lab_ref, own_ref, out_ref):
    seg_tile = pl.program_id(0)
    inp_tile = pl.program_id(1)

    @pl.when(inp_tile == 0)
    def _init():
        out_ref[...] = own_ref[...]

    local = dst_ref[...] - seg_tile * SEG_BLOCK
    member = local[:, None] == jnp.arange(SEG_BLOCK, dtype=jnp.int32)[None, :]
    cand = jnp.where(member, lab_ref[...][:, None], jnp.int32(_INT_MAX))
    out_ref[...] = jnp.minimum(out_ref[...], jnp.min(cand, axis=0))


@functools.partial(jax.jit, static_argnames=("num_vertices", "interpret"))
def edge_min_label(src: jax.Array, dst: jax.Array, valid: jax.Array,
                   labels: jax.Array, num_vertices: int,
                   interpret: bool = True) -> jax.Array:
    """One propagation step: ``min(labels[v], min_{(u,v)} labels[u])``.

    ``interpret=True`` runs the kernel body in Python on the host (the CPU
    backend); on TPU pass ``interpret=False``.
    """
    lab = labels.astype(jnp.int32)
    dst_p, lab_p, grid, s_pad = pad_coo(
        src, dst, valid, lab, num_vertices, TILE, SEG_BLOCK, fill=_INT_MAX)
    own = jnp.pad(lab[:num_vertices], (0, s_pad - num_vertices),
                  constant_values=_INT_MAX)
    out = pl.pallas_call(
        _minlabel_kernel,
        name="edge_min_label",
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE,), lambda s, i: (i,)),
            pl.BlockSpec((TILE,), lambda s, i: (i,)),
            pl.BlockSpec((SEG_BLOCK,), lambda s, i: (s,)),
        ],
        out_specs=pl.BlockSpec((SEG_BLOCK,), lambda s, i: (s,)),
        out_shape=jax.ShapeDtypeStruct((s_pad,), jnp.int32),
        interpret=interpret,
    )(dst_p, lab_p, own)
    return out[:num_vertices]
