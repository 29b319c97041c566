"""Pallas TPU kernel: Bloom-filter build/probe (semi-join prefilter).

Beyond-paper optimization: the compiled pipeline builds a Bloom filter of
each join's build-side keys and drops probe rows that cannot match before
the capacity expansion (``relational/join.py:join_with_capacity``).

Build uses the same one-hot/max trick as the histogram kernel (OR-scatter),
with the bitset tiled over a grid axis like the histogram's segments.
Probe re-hashes and reads its bits from the VMEM-resident bitset held as a
(bits/128, 128) matrix: a one-hot row select on the MXU, then a lane pick.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE = 2048
BITS_BLOCK = 2048     # bitset columns per build grid step
LANES = 128           # probe-side bitset row width


def _hash(ks: jnp.ndarray, num_bits: int, i: int) -> jnp.ndarray:
    h = ks.astype(jnp.uint32) * jnp.uint32(2654435761 + 40503 * i) \
        + jnp.uint32(i * 97)
    h ^= h >> 15
    return (h % jnp.uint32(num_bits)).astype(jnp.int32)


def _build_kernel(keys_ref, valid_ref, bits_ref, *, num_bits: int,
                  num_hashes: int):
    bit_tile = pl.program_id(0)
    inp_tile = pl.program_id(1)

    @pl.when(inp_tile == 0)
    def _init():
        bits_ref[...] = jnp.zeros_like(bits_ref)

    keys = keys_ref[...]
    valid = valid_ref[...] != 0
    block = bits_ref.shape[0]
    positions = jnp.arange(block, dtype=jnp.int32)
    acc = bits_ref[...]
    for i in range(num_hashes):
        # mask folded into the index (-1 hits no bit): Mosaic cannot
        # broadcast a 1-D bool vector to a column
        local = jnp.where(valid, _hash(keys, num_bits, i) - bit_tile * block,
                          -1)
        onehot = (local[:, None] == positions[None, :]).astype(jnp.int32)
        acc = jnp.maximum(acc, jnp.max(onehot, axis=0))
    bits_ref[...] = acc


def _probe_kernel(bits_ref, keys_ref, out_ref, *, num_bits: int,
                  num_hashes: int):
    bits = bits_ref[...]                       # (num_bits // LANES, LANES)
    keys = keys_ref[...]
    rows = jnp.arange(bits.shape[0], dtype=jnp.int32)
    lanes = jnp.arange(LANES, dtype=jnp.int32)
    hit = jnp.ones(keys.shape, dtype=jnp.int32)
    for i in range(num_hashes):
        pos = _hash(keys, num_bits, i)
        # 2-D gather bits[pos // LANES, pos % LANES]: a one-hot row select
        # on the MXU (0/1 values, exact at any precision), then a lane pick
        row_sel = (pos[:, None] // LANES == rows[None, :]).astype(jnp.float32)
        picked = jnp.dot(row_sel, bits, preferred_element_type=jnp.float32)
        lane_sel = (pos[:, None] % LANES) == lanes[None, :]
        bit = jnp.sum(jnp.where(lane_sel, picked, 0.0), axis=1)
        hit = hit * (bit > 0).astype(jnp.int32)
    out_ref[...] = hit


@functools.partial(jax.jit,
                   static_argnames=("num_bits", "num_hashes", "interpret"))
def bloom_build(keys: jax.Array, valid: jax.Array, num_bits: int,
                num_hashes: int = 2, interpret: bool = True) -> jax.Array:
    n = keys.shape[0]
    if n == 0:
        # zero grid steps would leave the output uninitialized
        return jnp.zeros((num_bits,), jnp.int32)
    n_pad = ((n + TILE - 1) // TILE) * TILE
    ks = jnp.pad(keys.astype(jnp.int32), (0, n_pad - n))
    vm = jnp.pad(valid.astype(jnp.int32), (0, n_pad - n))
    block = min(num_bits, BITS_BLOCK)
    kernel = functools.partial(_build_kernel, num_bits=num_bits,
                               num_hashes=num_hashes)
    return pl.pallas_call(
        kernel,
        name="bloom_build",
        grid=(num_bits // block, n_pad // TILE),
        in_specs=[
            pl.BlockSpec((TILE,), lambda b, i: (i,)),
            pl.BlockSpec((TILE,), lambda b, i: (i,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda b, i: (b,)),
        out_shape=jax.ShapeDtypeStruct((num_bits,), jnp.int32),
        interpret=interpret,
    )(ks, vm)


@functools.partial(jax.jit, static_argnames=("num_hashes", "interpret"))
def bloom_probe(bits: jax.Array, keys: jax.Array, num_hashes: int = 2,
                interpret: bool = True) -> jax.Array:
    num_bits = bits.shape[0]
    n = keys.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.bool_)
    n_pad = ((n + TILE - 1) // TILE) * TILE
    ks = jnp.pad(keys.astype(jnp.int32), (0, n_pad - n))
    bits2d = bits.astype(jnp.float32).reshape(num_bits // LANES, LANES)
    kernel = functools.partial(_probe_kernel, num_bits=num_bits,
                               num_hashes=num_hashes)
    out = pl.pallas_call(
        kernel,
        name="bloom_probe",
        grid=(n_pad // TILE,),
        in_specs=[
            pl.BlockSpec(bits2d.shape, lambda i: (0, 0)),     # stationary
            pl.BlockSpec((TILE,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((TILE,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.int32),
        interpret=interpret,
    )(bits2d, ks)
    return out[:n] > 0
