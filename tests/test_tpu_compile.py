"""AOT compiles of the graph-path Pallas kernels for a described TPU v5e.

Interpret mode (the rest of the suite) cannot show what the chip's
compiler refuses: 1-D gathers inside a kernel, 1-D mask broadcasts, blocks
over the fast-memory budget.  These tests compile every kernel with
``interpret=False`` for one chip of a described ``v5e:2x2`` topology, at
the widths of the fraud model on TPC-DS at published row counts
(``make_tpcds(sf=1000)``: 2.88M store_sales rows, so 2.88M edges per label
over ~600k vertices; a 500k-row customer build side for the join probe).
Each kernel's HLO instruction carries its pinned ``pallas_call`` name.
Nothing runs: a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and under
several test workers the others would fail or collect different tests.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.pipeline import persistent_compilation_cache_off
from repro.kernels import (
    bloom,
    frontier,
    label_prop,
    segment_csr,
    sorted_probe,
    spmv,
)

EDGES = 2_880_000
VERTICES = 600_000
BUILD_ROWS = 500_000
BLOOM_BITS = 16_384          # ops.bloom_bits_for's cap


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    with persistent_compilation_cache_off():
        yield SingleDeviceSharding(topo.devices[0])


def _cases():
    i32, f32, b = jnp.int32, jnp.float32, jnp.bool_
    edge = [((EDGES,), i32), ((EDGES,), i32), ((EDGES,), b)]
    return {
        "sorted_probe": (
            lambda s, p: sorted_probe.sorted_probe(s, p, interpret=False),
            [((BUILD_ROWS,), i32), ((EDGES,), i32)]),
        "bloom_build": (
            lambda k, m: bloom.bloom_build(k, m, BLOOM_BITS, interpret=False),
            [((EDGES,), i32), ((EDGES,), b)]),
        "bloom_probe": (
            lambda bits, k: bloom.bloom_probe(bits, k, interpret=False),
            [((BLOOM_BITS,), i32), ((EDGES,), i32)]),
        "segment_counts": (
            lambda v, m: segment_csr.segment_counts(v, m, VERTICES,
                                                    interpret=False),
            [((EDGES,), i32), ((EDGES,), b)]),
        "edge_spmv": (
            lambda s, d, m, x: spmv.edge_spmv(s, d, m, x, VERTICES,
                                              interpret=False),
            edge + [((VERTICES,), f32)]),
        "edge_min_label": (
            lambda s, d, m, x: label_prop.edge_min_label(
                s, d, m, x, VERTICES, interpret=False),
            edge + [((VERTICES,), i32)]),
        "frontier_expand": (
            lambda s, d, m, f, v: frontier.frontier_expand(
                s, d, m, f, v, VERTICES, interpret=False),
            edge + [((VERTICES,), b), ((VERTICES,), b)]),
    }


@pytest.mark.parametrize("kernel", sorted(_cases()))
def test_kernel_compiles_for_v5e(one_chip, kernel):
    fn, shapes = _cases()[kernel]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel's pinned name is its HLO instruction's, which a profile
    # shows and the benchmark's roofline readers match
    assert re.search(rf"%{kernel}\.\d+ = ", text), kernel
