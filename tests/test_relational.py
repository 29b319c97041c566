"""Unit tests for the static-shape relational substrate.

Hypothesis property tests live in test_properties.py (optional dep).
"""
import numpy as np
import pytest

from repro.relational import (
    Table,
    sort_merge_join,
    join_count,
    semi_join_mask,
    filter_table,
    dedup,
    compact,
    concat,
)


def test_inner_join_basic():
    left = Table.from_arrays(k=np.array([1, 2, 2, 3], np.int32),
                             a=np.array([10, 20, 21, 30], np.int32))
    right = Table.from_arrays(k=np.array([2, 2, 3, 9], np.int32),
                              b=np.array([100, 101, 200, 900], np.int32))
    out = sort_merge_join(left.prefix("L"), right.prefix("R"),
                          on=[("L.k", "R.k")])
    rows = out.to_rowset(["L.a", "R.b"])
    want = {(20, 100, 0), (20, 101, 0), (21, 100, 0), (21, 101, 0),
            (30, 200, 0)}
    assert rows == want


def test_left_outer_join_nulls():
    left = Table.from_arrays(k=np.array([1, 2, 5], np.int32),
                             a=np.array([10, 20, 50], np.int32))
    right = Table.from_arrays(k=np.array([2, 2], np.int32),
                              b=np.array([7, 8], np.int32))
    out = sort_merge_join(left.prefix("L"), right.prefix("R"),
                          on=[("L.k", "R.k")], how="left_outer",
                          indicator="__nn__R")
    data = out.to_numpy()
    # every left row appears; unmatched rows have indicator False
    assert sorted(data["L.a"].tolist()) == [10, 20, 20, 50]
    matched = {(a, m) for a, m in zip(data["L.a"].tolist(),
                                      data["__nn__R"].tolist())}
    assert (10, False) in matched and (50, False) in matched
    assert (20, True) in matched


def test_join_respects_validity():
    left = Table.from_arrays(k=np.array([1, 2], np.int32))
    left = left.mask(np.array([True, False]))
    right = Table.from_arrays(k2=np.array([1, 2], np.int32))
    out = sort_merge_join(left.prefix("L"), right.prefix("R"),
                          on=[("L.k", "R.k2")])
    assert int(out.num_rows()) == 1


def test_two_column_key_and_post_filter():
    left = Table.from_arrays(x=np.array([1, 1, 2], np.int32),
                             y=np.array([5, 6, 5], np.int32),
                             z=np.array([9, 9, 8], np.int32))
    right = Table.from_arrays(x=np.array([1, 1], np.int32),
                              y=np.array([5, 6], np.int32),
                              z=np.array([9, 7], np.int32))
    out = sort_merge_join(
        left.prefix("L"), right.prefix("R"),
        on=[("L.x", "R.x"), ("L.y", "R.y"), ("L.z", "R.z")])
    rows = out.to_rowset(["L.x", "L.y"])
    assert rows == {(1, 5, 0)}  # (1,6) killed by z post-filter


def test_static_capacity_path_matches_dynamic():
    rng = np.random.default_rng(0)
    lk = rng.integers(0, 20, size=64).astype(np.int32)
    rk = rng.integers(0, 20, size=48).astype(np.int32)
    left = Table.from_arrays(k=lk).prefix("L")
    right = Table.from_arrays(k=rk).prefix("R")
    dyn = sort_merge_join(left, right, on=[("L.k", "R.k")])
    n = int(join_count(left, right, ("L.k",), ("R.k",)))
    stat = sort_merge_join(left, right, on=[("L.k", "R.k")],
                           capacity=max(8, n))
    assert dyn.to_rowset() == stat.to_rowset()


def test_semi_join_mask():
    left = Table.from_arrays(k=np.array([1, 2, 3], np.int32))
    right = Table.from_arrays(j=np.array([2, 2, 9], np.int32))
    m = semi_join_mask(left, right, on=[("k", "j")])
    assert np.asarray(m).tolist() == [False, True, False]


def test_filter_dedup_compact_concat():
    t = Table.from_arrays(k=np.array([3, 1, 3, 2, 1], np.int32),
                          v=np.array([0, 1, 2, 3, 4], np.int32))
    f = filter_table(t, "k", ">=", 2)
    assert sorted(f.to_numpy()["k"].tolist()) == [2, 3, 3]
    d = dedup(t, ["k"])
    assert sorted(d.to_numpy()["k"].tolist()) == [1, 2, 3]
    c = compact(f)
    v = np.asarray(c.valid)
    assert v[: int(f.num_rows())].all() and not v[int(f.num_rows()):].any()
    cc = concat([t, t])
    assert int(cc.num_rows()) == 10


def _bloom_hits_np(keys, bits, num_hashes=2):
    """Numpy twin of the Bloom probe: every hashed bit of a key is set."""
    ks = keys.astype(np.uint32)
    hit = np.ones(keys.shape, bool)
    for i in range(num_hashes):
        h = ks * np.uint32(2654435761 + 40503 * i) + np.uint32(i * 97)
        h ^= h >> np.uint32(15)
        hit &= bits[(h % np.uint32(bits.size)).astype(np.int64)] > 0
    return hit


def test_bloom_prefilter_counts_match_numpy():
    """The join's (probed, passed) pair equals a numpy count over the same
    probe keys and bitset; passed <= probed, and no key that matches is
    filtered out.  Without a prefilter the join returns no counts."""
    from repro.kernels import ops as kops
    from repro.relational.join import NULL_KEY64, join_with_capacity

    rng = np.random.default_rng(3)
    lk = rng.integers(0, 5000, size=900).astype(np.int32)
    lk[::17] = NULL_KEY64                       # null keys are not probed
    rk = rng.integers(0, 5000, size=300).astype(np.int32)
    left = Table.from_arrays(k=lk).mask(np.arange(900) % 11 != 0).prefix("L")
    right = Table.from_arrays(k=rk).prefix("R")
    bits_n = 256                                # small: some keys collide
    _, required, counts = join_with_capacity(
        left, right, [("L.k", "R.k")], capacity=4096, bloom_bits=bits_n)
    probed, passed = (int(x) for x in np.asarray(counts))

    bits = np.asarray(kops.bloom_build(
        np.asarray(right["R.k"]), np.asarray(right.valid), bits_n))
    keyed = np.asarray(left.valid) & (lk != NULL_KEY64)
    assert probed == int(keyed.sum())
    assert passed == int((keyed & _bloom_hits_np(lk, bits)).sum())
    assert int((keyed & np.isin(lk, rk)).sum()) <= passed < probed

    _, required_off, none = join_with_capacity(
        left, right, [("L.k", "R.k")], capacity=4096)
    assert none is None
    assert int(required_off) == int(required)


I32_MIN, I32_MAX = -2**31, 2**31 - 1


def _range_case(lk, rk, lvalid=None, rvalid=None, key_range=None):
    lk, rk = np.asarray(lk, np.int64), np.asarray(rk, np.int64)
    lvalid = np.ones(lk.shape, bool) if lvalid is None else np.asarray(lvalid)
    rvalid = np.ones(rk.shape, bool) if rvalid is None else np.asarray(rvalid)
    if key_range is None:                     # as the pipeline plans it
        from repro.relational.join import round_capacity

        live = rk[rvalid]
        key_range = (int(live.min()), round_capacity(int(np.ptp(live))))
    return lk.astype(np.int32), lvalid, rk.astype(np.int32), rvalid, key_range


_RANGE_CASES = {
    "unique_dense": _range_case(np.arange(40) % 23, np.arange(1, 21)),
    "non_unique": _range_case([3, 4, 4, 9, 1, 7, 7, 7],
                              [4, 4, 7, 3, 3, 3, 9, 4, 8]),
    "nulls_either_side": _range_case(
        [5, 6, 7, 8, 5, I32_MAX], [5, 6, 7, 9, 6],
        lvalid=[True, False, True, True, True, True],
        rvalid=[True, True, False, True, True]),
    "probe_outside_range": _range_case([-100, 0, 1, 2, 3, 10, 11, 999],
                                       [1, 2, 2, 3, 10]),
    "negative": _range_case([-9, -5, -5, -1, 0, 3], [-7, -5, -5, -3, 0]),
    "int32_min": _range_case(
        [I32_MIN, I32_MIN + 1, I32_MIN + 4, I32_MAX, 0, I32_MAX - 1],
        [I32_MIN, I32_MIN, I32_MIN + 4, I32_MIN + 2]),
    "int32_max": _range_case([I32_MAX - 1, I32_MAX, I32_MAX - 5, 3, I32_MIN],
                             [I32_MAX - 1, I32_MAX - 3, I32_MAX - 1]),
    "all_invalid_build": _range_case([1, 2, 3], [1, 2, 3],
                                     rvalid=[False] * 3, key_range=(0, 8)),
    "span_of_one": _range_case([4, 5, 6, 5, I32_MAX], [5, 5, 5],
                               key_range=(5, 1)),
}


@pytest.mark.parametrize("case", sorted(_RANGE_CASES))
def test_range_probe_equals_bisection(case):
    """The range table returns the bisection's (lo, hi) for every probe
    key, so the traced joins' whole output tables are bit-identical:
    inner, native left outer, and left outer with a post-filter
    condition."""
    import jax.numpy as jnp

    from repro.relational.join import (
        _probe_ranges,
        composite_key,
        join_with_capacity,
        left_outer_with_capacity,
        probe_tally,
    )

    lk, lvalid, rk, rvalid, key_range = _RANGE_CASES[case]
    left = Table.from_arrays(k=lk, a=np.arange(lk.size, dtype=np.int32),
                             z=lk % 3).mask(lvalid).prefix("L")
    right = Table.from_arrays(k=rk, b=np.arange(100, 100 + rk.size,
                                                dtype=np.int32),
                              z=rk % 2).mask(rvalid).prefix("R")
    lkey = composite_key(left, ("L.k",))
    rkey = composite_key(right, ("R.k",))
    rk_sorted = jnp.sort(rkey)
    want = _probe_ranges(rk_sorted, lkey, False)
    got = _probe_ranges(rk_sorted, lkey, False, rkey, key_range)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    probed, outside = (int(x) for x in np.asarray(
        probe_tally(left, right, [("L.k", "R.k")], key_range)))
    assert probed == int((lvalid & (lk != I32_MAX)).sum())
    assert outside == 0

    def same(a, b):
        assert a.column_names() == b.column_names()
        for name in a.column_names():
            np.testing.assert_array_equal(np.asarray(a[name]),
                                          np.asarray(b[name]), name)
        np.testing.assert_array_equal(np.asarray(a.valid),
                                      np.asarray(b.valid))

    on = [("L.k", "R.k")]
    joins = [
        lambda kr: join_with_capacity(left, right, on, capacity=64,
                                      key_range=kr),
        lambda kr: left_outer_with_capacity(left, right, on, "m", 64,
                                            key_range=kr),
        lambda kr: left_outer_with_capacity(left, right,
                                            on + [("L.z", "R.z")], "m", 64,
                                            key_range=kr),
    ]
    for join in joins:
        (t_range, n_range, _), (t_search, n_search, _) = join(key_range), \
            join(None)
        assert int(n_range) == int(n_search)
        same(t_range, t_search)


def test_probe_tally_counts_build_keys_outside_the_range():
    from repro.relational.join import probe_tally

    left = Table.from_arrays(k=np.array([1, 2, I32_MAX], np.int32))
    right = Table.from_arrays(k=np.array([0, 1, 5, 8, 9, I32_MAX], np.int32))
    right = right.mask(np.array([True, True, True, True, False, True]))
    tally = probe_tally(left, right, [("k", "k")], (1, 7))
    # 0 is under kmin and 8 at kmin + span; 9 is invalid, I32_MAX a NULL
    assert np.asarray(tally).tolist() == [2, 2]
    assert np.asarray(probe_tally(left, right, [("k", "k")])).tolist() \
        == [2, 0]
