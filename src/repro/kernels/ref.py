"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each function is the semantic reference the kernels are tested against
(tests sweep shapes/dtypes and assert_allclose kernel-vs-ref).
"""
from __future__ import annotations

import jax.numpy as jnp


def sorted_probe(sorted_keys: jnp.ndarray, probe_keys: jnp.ndarray):
    """Two-sided binary search: (lo, hi) match ranges per probe key."""
    lo = jnp.searchsorted(sorted_keys, probe_keys, side="left")
    hi = jnp.searchsorted(sorted_keys, probe_keys, side="right")
    return lo.astype(jnp.int32), hi.astype(jnp.int32)


def segment_counts(values: jnp.ndarray, valid: jnp.ndarray,
                   num_segments: int) -> jnp.ndarray:
    """Histogram of ``values`` (masked) into ``num_segments`` bins."""
    return (
        jnp.zeros((num_segments,), jnp.int32)
        .at[values]
        .add(valid.astype(jnp.int32), mode="drop")
    )


def edge_spmv(src: jnp.ndarray, dst: jnp.ndarray, valid: jnp.ndarray,
              x: jnp.ndarray, num_vertices: int) -> jnp.ndarray:
    """Push SpMV over COO edges: ``y[v] = sum_{valid (u,v)} x[u]``."""
    contrib = jnp.where(valid, x.astype(jnp.float32)[src], 0.0)
    return (
        jnp.zeros((num_vertices,), jnp.float32)
        .at[dst]
        .add(jnp.where(valid, contrib, 0.0), mode="drop")
    )


def edge_min_label(src: jnp.ndarray, dst: jnp.ndarray, valid: jnp.ndarray,
                   labels: jnp.ndarray, num_vertices: int) -> jnp.ndarray:
    """One min-label propagation step (identity included)."""
    int_max = jnp.int32(2**31 - 1)
    lab = labels.astype(jnp.int32)
    incoming = jnp.where(valid, lab[src], int_max)
    return lab[:num_vertices].at[dst].min(incoming, mode="drop")


def frontier_expand(src: jnp.ndarray, dst: jnp.ndarray, valid: jnp.ndarray,
                    frontier: jnp.ndarray, visited: jnp.ndarray,
                    num_vertices: int) -> jnp.ndarray:
    """One BFS level: newly reached = touched-by-frontier and not visited."""
    hit = (valid & frontier.astype(bool)[src]).astype(jnp.int32)
    reached = (
        jnp.zeros((num_vertices,), jnp.int32).at[dst].max(hit, mode="drop")
    ) > 0
    return reached & ~visited.astype(bool)


def _bloom_hashes(keys: jnp.ndarray, num_bits: int, num_hashes: int):
    """Cheap multiplicative hashes -> (num_hashes, N) bit positions."""
    ks = keys.astype(jnp.uint32)
    out = []
    for i in range(num_hashes):
        h = ks * jnp.uint32(2654435761 + 40503 * i) + jnp.uint32(i * 97)
        h ^= h >> 15
        out.append((h % jnp.uint32(num_bits)).astype(jnp.int32))
    return jnp.stack(out)


def bloom_build(keys: jnp.ndarray, valid: jnp.ndarray, num_bits: int,
                num_hashes: int = 2) -> jnp.ndarray:
    """Bloom bitset (int32 0/1 per bit — word-packing left to the kernel)."""
    pos = _bloom_hashes(keys, num_bits, num_hashes)
    bits = jnp.zeros((num_bits,), jnp.int32)
    for i in range(num_hashes):
        bits = bits.at[pos[i]].max(valid.astype(jnp.int32))
    return bits


def bloom_probe(bits: jnp.ndarray, keys: jnp.ndarray,
                num_hashes: int = 2) -> jnp.ndarray:
    """True where the key is possibly present (no false negatives)."""
    num_bits = bits.shape[0]
    pos = _bloom_hashes(keys, num_bits, num_hashes)
    hit = jnp.ones(keys.shape, dtype=bool)
    for i in range(num_hashes):
        hit = hit & (bits[pos[i]] > 0)
    return hit

