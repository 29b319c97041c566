"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle.

Hypothesis property sweeps live in test_properties.py (optional dep).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.bloom import bloom_build, bloom_probe
from repro.kernels.segment_csr import segment_counts
from repro.kernels.sorted_probe import sorted_probe


@pytest.mark.parametrize("n_sorted", [1, 7, 128, 1000, 5000])
@pytest.mark.parametrize("n_probe", [1, 63, 1024, 3000])
def test_sorted_probe_shapes(n_sorted, n_probe):
    rng = np.random.default_rng(n_sorted * 31 + n_probe)
    sk = np.sort(rng.integers(0, 500, n_sorted)).astype(np.int32)
    pk = rng.integers(-5, 505, n_probe).astype(np.int32)
    lo, hi = sorted_probe(jnp.asarray(sk), jnp.asarray(pk), interpret=True)
    rlo, rhi = ref.sorted_probe(jnp.asarray(sk), jnp.asarray(pk))
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(rlo))
    np.testing.assert_array_equal(np.asarray(hi), np.asarray(rhi))


@pytest.mark.parametrize("n", [1, 100, 2048, 5000])
@pytest.mark.parametrize("segs", [1, 7, 100, 3000])
def test_segment_counts(n, segs):
    rng = np.random.default_rng(n + segs)
    vals = rng.integers(0, segs, n).astype(np.int32)
    valid = rng.random(n) < 0.8
    got = segment_counts(jnp.asarray(vals), jnp.asarray(valid), segs,
                         interpret=True)
    want = ref.segment_counts(jnp.asarray(vals), jnp.asarray(valid), segs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(np.asarray(got).sum()) == int(valid.sum())


@pytest.mark.parametrize("n,bits", [(50, 128), (1000, 512), (4096, 4096)])
@pytest.mark.parametrize("num_hashes", [1, 2, 3])
def test_bloom_no_false_negatives(n, bits, num_hashes):
    rng = np.random.default_rng(n + bits)
    keys = rng.integers(0, 10_000, n).astype(np.int32)
    valid = rng.random(n) < 0.9
    b = bloom_build(jnp.asarray(keys), jnp.asarray(valid), bits,
                    num_hashes, interpret=True)
    rb = ref.bloom_build(jnp.asarray(keys), jnp.asarray(valid), bits,
                         num_hashes)
    np.testing.assert_array_equal(np.asarray(b), np.asarray(rb))
    # every valid inserted key must probe True (no false negatives)
    hits = bloom_probe(b, jnp.asarray(keys[valid]), num_hashes,
                       interpret=True)
    assert bool(np.asarray(hits).all())
    rhits = ref.bloom_probe(rb, jnp.asarray(keys), num_hashes)
    hits_all = bloom_probe(b, jnp.asarray(keys), num_hashes, interpret=True)
    np.testing.assert_array_equal(np.asarray(hits_all), np.asarray(rhits))


NULL32 = np.int32(2**31 - 1)


def _probe_parity(sk, pk):
    lo, hi = sorted_probe(jnp.asarray(sk), jnp.asarray(pk), interpret=True)
    rlo, rhi = ref.sorted_probe(jnp.asarray(sk), jnp.asarray(pk))
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(rlo))
    np.testing.assert_array_equal(np.asarray(hi), np.asarray(rhi))


def test_sorted_probe_empty_probe_side():
    sk = np.sort(np.arange(100, dtype=np.int32))
    _probe_parity(sk, np.zeros((0,), np.int32))


def test_sorted_probe_empty_build_side():
    _probe_parity(np.zeros((0,), np.int32),
                  np.array([-3, 0, 7], np.int32))


def test_sorted_probe_all_null_keys():
    """NULL_KEY (int32 max) probes and build tails must bisect exactly like
    the reference — the join layer relies on NULLs sorting last."""
    sk = np.sort(np.array([1, 5, 5, NULL32, NULL32], np.int32))
    pk = np.array([NULL32, NULL32, 5, 0], np.int32)
    _probe_parity(sk, pk)
    _probe_parity(np.full(16, NULL32, np.int32), np.full(7, NULL32, np.int32))


def test_sorted_probe_build_spans_multiple_probe_blocks():
    rng = np.random.default_rng(7)
    sk = np.sort(rng.integers(0, 10_000, 6000).astype(np.int32))
    pk = rng.integers(-100, 10_100, 2500).astype(np.int32)
    _probe_parity(sk, pk)


def test_sorted_probe_keys_outside_build_range():
    sk = np.sort(np.array([10, 20, 20, 30], np.int32))
    pk = np.array([-2**31, -1, 9, 31, 2**31 - 2], np.int32)
    _probe_parity(sk, pk)


def test_bloom_empty_build_side():
    bits = bloom_build(jnp.zeros((0,), jnp.int32), jnp.zeros((0,), bool),
                       128, interpret=True)
    rbits = ref.bloom_build(jnp.zeros((0,), jnp.int32),
                            jnp.zeros((0,), bool), 128)
    np.testing.assert_array_equal(np.asarray(bits), np.asarray(rbits))
    assert int(np.asarray(bits).sum()) == 0
    hits = bloom_probe(bits, jnp.asarray(np.array([1, 2, 3], np.int32)),
                       interpret=True)
    assert not np.asarray(hits).any()


def test_bloom_empty_probe_side():
    keys = jnp.asarray(np.arange(10, dtype=np.int32))
    bits = bloom_build(keys, jnp.ones((10,), bool), 128, interpret=True)
    hits = bloom_probe(bits, jnp.zeros((0,), jnp.int32), interpret=True)
    assert np.asarray(hits).shape == (0,)


def test_bloom_all_null_build_keys():
    """An all-invalid (all-NULL) build side must set no bits at all."""
    keys = jnp.asarray(np.full(100, NULL32, np.int32))
    valid = jnp.zeros((100,), bool)
    bits = bloom_build(keys, valid, 256, interpret=True)
    rbits = ref.bloom_build(keys, valid, 256)
    np.testing.assert_array_equal(np.asarray(bits), np.asarray(rbits))
    assert int(np.asarray(bits).sum()) == 0


def test_bloom_build_spans_multiple_tiles():
    """5000 keys > 2 TILEs: bit-OR accumulation across grid steps."""
    rng = np.random.default_rng(11)
    keys = rng.integers(0, 50_000, 5000).astype(np.int32)
    valid = rng.random(5000) < 0.7
    bits = bloom_build(jnp.asarray(keys), jnp.asarray(valid), 1024,
                       num_hashes=2, interpret=True)
    rbits = ref.bloom_build(jnp.asarray(keys), jnp.asarray(valid), 1024,
                            num_hashes=2)
    np.testing.assert_array_equal(np.asarray(bits), np.asarray(rbits))
    hits = bloom_probe(bits, jnp.asarray(keys[valid]), interpret=True)
    assert bool(np.asarray(hits).all()), "false negative"


def test_bloom_probe_keys_outside_build_range():
    rng = np.random.default_rng(13)
    keys = rng.integers(0, 1000, 500).astype(np.int32)
    bits = bloom_build(jnp.asarray(keys), jnp.ones((500,), bool), 2048,
                       interpret=True)
    outside = np.array([-5, 10_001, 2**31 - 2, NULL32], np.int32)
    got = bloom_probe(bits, jnp.asarray(outside), interpret=True)
    want = ref.bloom_probe(jnp.asarray(np.asarray(bits)),
                           jnp.asarray(outside))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_csr_offsets_kernel_path():
    from repro.graph import csr_offsets
    vals = jnp.asarray(np.array([0, 1, 1, 3, 3, 3], np.int32))
    valid = jnp.asarray(np.array([True] * 6))
    off_k = csr_offsets(vals, valid, 5, use_kernel=True)
    off_j = csr_offsets(vals, valid, 5, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(off_k), np.asarray(off_j))


@pytest.mark.parametrize("on_tpu", [False, True])
def test_kernel_policy_keeps_probe_off_by_default(monkeypatch, on_tpu):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: on_tpu)
    assert ops.resolve_use_kernel(None) is on_tpu
    assert ops.resolve_use_kernel(None, probe=True) is False
    assert ops.resolve_use_kernel(True, probe=True) is True
    assert ops.resolve_use_kernel(False) is False

