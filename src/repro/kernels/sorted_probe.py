"""Pallas TPU kernel: two-sided rank search (join probe).

This is the probe phase of the sort-merge join.  For each probe key ``p``
it returns ``lo = #{build keys < p}`` and ``hi = #{build keys <= p}`` over
the sorted build side, the same ``(lo, hi)`` ranges as two
``searchsorted`` calls.

TPU adaptation: Mosaic lowers no 1-D gather inside a kernel, so a
lock-step bisection cannot read ``sorted[mid]``.  The kernel ranks by
counting instead: probe keys are tiled over the first grid axis, the build
side streams through VMEM in BUILD_BLOCK slices over the second, and every
step adds the lane-parallel comparison counts of one (probe tile, build
block) pair into the output block (the accumulate pattern of
``segment_csr``).  It compiles at any build size, but its work grows with
probe x build rows, so :func:`repro.kernels.ops.resolve_use_kernel` leaves
it off the TPU default: the join probe there runs XLA ``searchsorted``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

PROBE_BLOCK = 1024
BUILD_BLOCK = 1024

_INT_MAX = 2**31 - 1  # python int: jnp scalars would be captured as consts


def _rank_kernel(sorted_ref, probe_ref, lo_ref, hi_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        lo_ref[...] = jnp.zeros_like(lo_ref)
        hi_ref[...] = jnp.zeros_like(hi_ref)

    keys = sorted_ref[...][None, :]
    probes = probe_ref[...][:, None]
    lo_ref[...] += jnp.sum((keys < probes).astype(jnp.int32), axis=1)
    hi_ref[...] += jnp.sum((keys <= probes).astype(jnp.int32), axis=1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sorted_probe(sorted_keys: jax.Array, probe_keys: jax.Array,
                 interpret: bool = True):
    """(lo, hi) match ranges of each probe key in ``sorted_keys``.

    ``interpret=True`` runs the kernel body in Python on the host (the CPU
    backend); on TPU pass ``interpret=False``.
    """
    n_sorted = sorted_keys.shape[0]
    n_probe = probe_keys.shape[0]
    if n_probe == 0:
        empty = jnp.zeros((0,), jnp.int32)
        return empty, empty
    if n_sorted == 0:
        zeros = jnp.zeros((n_probe,), jnp.int32)
        return zeros, zeros
    p_pad = ((n_probe + PROBE_BLOCK - 1) // PROBE_BLOCK) * PROBE_BLOCK
    s_pad = ((n_sorted + BUILD_BLOCK - 1) // BUILD_BLOCK) * BUILD_BLOCK
    probe_padded = jnp.pad(probe_keys.astype(jnp.int32), (0, p_pad - n_probe))
    # int32-max padding sorts last; it can only be counted by a probe equal
    # to int32 max, and the clamp below removes it again
    sorted_padded = jnp.pad(sorted_keys.astype(jnp.int32),
                            (0, s_pad - n_sorted), constant_values=_INT_MAX)
    lo, hi = pl.pallas_call(
        _rank_kernel,
        name="sorted_probe",
        grid=(p_pad // PROBE_BLOCK, s_pad // BUILD_BLOCK),
        in_specs=[
            pl.BlockSpec((BUILD_BLOCK,), lambda i, b: (b,)),
            pl.BlockSpec((PROBE_BLOCK,), lambda i, b: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((PROBE_BLOCK,), lambda i, b: (i,)),
            pl.BlockSpec((PROBE_BLOCK,), lambda i, b: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p_pad,), jnp.int32),
            jax.ShapeDtypeStruct((p_pad,), jnp.int32),
        ],
        interpret=interpret,
    )(sorted_padded, probe_padded)
    return (jnp.minimum(lo[:n_probe], n_sorted),
            jnp.minimum(hi[:n_probe], n_sorted))
