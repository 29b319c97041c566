"""Jit'd public entry points for the Pallas kernels.

Each entry point compiles its kernel for the chip when JAX's default
backend is a TPU (:func:`_on_tpu`), and runs it in Pallas interpret mode
elsewhere, which is how the CPU test suite checks the kernels against
their :mod:`repro.kernels.ref` oracles.
"""
from __future__ import annotations

import jax

from repro.kernels import bloom as _bloom
from repro.kernels import frontier as _frontier
from repro.kernels import label_prop as _label_prop
from repro.kernels import segment_csr as _segment_csr
from repro.kernels import sorted_probe as _sorted_probe
from repro.kernels import spmv as _spmv


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_use_kernel(use_kernel=None, *, probe: bool = False) -> bool:
    """The one kernel-vs-reference policy: ``None`` auto-picks — Pallas
    kernels on TPU, their jnp references elsewhere (interpret-mode Pallas
    is emulation, not a fast path); ``True``/``False`` force it.

    The join probe (``probe=True``) is the exception: ``sorted_probe``
    ranks by counting, work that grows with probe x build rows, so the TPU
    default probes in XLA and only ``True`` selects the kernel.
    """
    if use_kernel is not None:
        return bool(use_kernel)
    return _on_tpu() and not probe


def sorted_probe(sorted_keys, probe_keys):
    return _sorted_probe.sorted_probe(
        sorted_keys, probe_keys, interpret=not _on_tpu())


def segment_counts(values, valid, num_segments: int):
    return _segment_csr.segment_counts(
        values, valid, num_segments, interpret=not _on_tpu())


def edge_spmv(src, dst, valid, x, num_vertices: int):
    return _spmv.edge_spmv(src, dst, valid, x, num_vertices,
                           interpret=not _on_tpu())


def edge_min_label(src, dst, valid, labels, num_vertices: int):
    return _label_prop.edge_min_label(src, dst, valid, labels, num_vertices,
                                      interpret=not _on_tpu())


def frontier_expand(src, dst, valid, frontier, visited, num_vertices: int):
    return _frontier.frontier_expand(src, dst, valid, frontier, visited,
                                     num_vertices, interpret=not _on_tpu())


def bloom_bits_for(build_capacity: int) -> int:
    """Pow-2 Bloom bitset size for a build side of ``build_capacity`` rows.

    ~2 bits per candidate key keeps the false-positive rate useful while the
    bitset stays VMEM-resident; clamped to [256, 16384] so tiny builds don't
    underfill a tile and the probe's stationary (bits/128, 128) block and
    its one-hot row select stay small.
    """
    import math

    raw = 1 << max(8, int(math.ceil(math.log2(max(2 * build_capacity, 1)))))
    return min(raw, 16384)


def bloom_build(keys, valid, num_bits: int, num_hashes: int = 2):
    return _bloom.bloom_build(
        keys, valid, num_bits, num_hashes, interpret=not _on_tpu())


def bloom_probe(bits, keys, num_hashes: int = 2):
    return _bloom.bloom_probe(bits, keys, num_hashes,
                              interpret=not _on_tpu())
