"""Differential tests: scatter-free segment reductions vs jax.ops.segment_*.

The toolkit (ops/segment.py) must match the scatter formulation bit-exactly
for integers (mod-2^64 contract) and to float tolerance for doubles, across
all strategy branches: one-hot limb matmul, broadcast-reduce, sorted prefix
tricks, and the scatter fallback itself.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from starrocks_tpu.ops import segment
from starrocks_tpu.ops.segment import (
    seg_count, seg_first_index, seg_max, seg_min, seg_sum, seg_sums,
)
from starrocks_tpu.runtime.config import config


def _rand_case(n, g, rng, big=False):
    gid = rng.integers(0, g + 1, size=n)  # g == dead marker
    if big:
        vals = rng.integers(-(2**62), 2**62, size=n, dtype=np.int64)
    else:
        vals = rng.integers(-1000, 1000, size=n, dtype=np.int64)
    return jnp.asarray(vals), jnp.asarray(gid, jnp.int32)


@pytest.mark.parametrize("n,g,big", [
    (4096, 8, False),      # matmul path, small G
    (4096, 8, True),       # matmul path, full-range int64 (wrap contract)
    (8192, 600, False),    # matmul path, medium G
    (1024 * 3, 7, False),  # non-power-of-two rows (block = 1024)
    (256, 5, False),       # tiny rows -> fallback
])
def test_seg_sum_int_matches_scatter(n, g, big):
    rng = np.random.default_rng(42 + n + g)
    vals, gid = _rand_case(n, g, rng, big)
    want = jax.ops.segment_sum(vals, gid, num_segments=g)
    got = jax.jit(lambda v, i: seg_sum(v, i, g))(vals, gid)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_seg_sum_sorted_int():
    rng = np.random.default_rng(7)
    n, g = 8192, 3000  # too many groups for matmul -> sorted cumsum path
    gid = np.sort(rng.integers(0, g, size=n)).astype(np.int32)
    vals = rng.integers(-(2**40), 2**40, size=n, dtype=np.int64)
    want = jax.ops.segment_sum(jnp.asarray(vals), jnp.asarray(gid), num_segments=g)
    got = jax.jit(lambda v, i: seg_sum(v, i, g, sorted_gid=True))(
        jnp.asarray(vals), jnp.asarray(gid))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_seg_sum_float_paths():
    rng = np.random.default_rng(3)
    n = 4096
    vals = jnp.asarray(rng.normal(size=n) * 1e3)
    # broadcast path (g <= 64)
    gid = jnp.asarray(rng.integers(0, 9, size=n), jnp.int32)
    want = jax.ops.segment_sum(vals, gid, num_segments=8)  # gid==8 dead
    got = seg_sum(vals, gid, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-12)
    # sorted path
    g2 = 500
    gid2 = jnp.asarray(np.sort(rng.integers(0, g2, size=n)), jnp.int32)
    want2 = jax.ops.segment_sum(vals, gid2, num_segments=g2)
    got2 = seg_sum(vals, gid2, g2, sorted_gid=True)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want2), rtol=1e-9)


def test_seg_count_single_limb():
    rng = np.random.default_rng(11)
    n, g = 65536, 40
    gid = jnp.asarray(rng.integers(0, g + 1, size=n), jnp.int32)
    live = jnp.asarray(rng.integers(0, 2, size=n), jnp.bool_)
    masked_gid = jnp.where(live, gid, g)
    want = jax.ops.segment_sum(jnp.asarray(live, jnp.int64), masked_gid,
                               num_segments=g)
    got = seg_count(live, masked_gid, g)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("sorted_gid", [False, True])
@pytest.mark.parametrize("is_min", [False, True])
def test_seg_minmax(sorted_gid, is_min):
    rng = np.random.default_rng(5)
    n, g = 4096, 20 if not sorted_gid else 300
    raw = rng.integers(0, g, size=n)
    gid = np.sort(raw) if sorted_gid else raw
    gid = jnp.asarray(gid, jnp.int32)
    ident = np.int64(2**62) if is_min else np.int64(-(2**62))
    vals = jnp.asarray(rng.integers(-10000, 10000, size=n, dtype=np.int64))
    ref = (jax.ops.segment_min if is_min else jax.ops.segment_max)(
        vals, gid, num_segments=g)
    fn = seg_min if is_min else seg_max
    got = fn(vals, gid, g, identity=ident, sorted_gid=sorted_gid)
    # empty groups: toolkit yields identity, scatter yields +/-inf-equivalent
    # extremes; compare only non-empty groups
    counts = np.asarray(jax.ops.segment_sum(jnp.ones(n, jnp.int32), gid,
                                            num_segments=g))
    mask = counts > 0
    np.testing.assert_array_equal(np.asarray(got)[mask], np.asarray(ref)[mask])


def test_seg_first_index():
    gid = jnp.asarray(np.array([0, 0, 2, 2, 2, 5], np.int32))
    got = np.asarray(seg_first_index(gid, 6, 6))
    np.testing.assert_array_equal(got, [0, 6, 2, 6, 6, 5])


@pytest.mark.parametrize("g,sorted_gid,formulation", [
    (1, False, "global"), (64, False, "masked"), (65, False, "contract"),
    (1024, False, "contract"), (1025, True, "sorted"),
    (1025, False, "scatter"),  # the last rung: many unsorted groups
])
def test_ladder_follows_group_count_and_sortedness(g, sorted_gid,
                                                   formulation):
    """Which formulation sums an aggregate's integers follows the group
    count and whether gid is sorted, nothing else: the same ladder on
    every backend."""
    rng = np.random.default_rng(g)
    n = 4096
    raw = rng.integers(0, g + 1, size=n)  # g == dead marker
    gid = jnp.asarray(np.sort(raw) if sorted_gid else raw, jnp.int32)
    vals = jnp.asarray(rng.integers(-(2**40), 2**40, size=n, dtype=np.int64))
    info = {}
    (got,) = seg_sums([(vals, 64)], gid, g, sorted_gid=sorted_gid, info=info)
    assert info["formulation"] == formulation
    want = jax.ops.segment_sum(vals, gid, num_segments=g)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_seg_sum_float_sorted_no_cancellation():
    """A small group after a huge-magnitude group must not lose precision
    to a global prefix sum (regression: cumsum-diff cancellation)."""
    n, g = 2048, 300  # > bcast max -> sorted float path
    gid = np.sort(np.concatenate([
        np.zeros(20, np.int32), np.ones(20, np.int32),
        np.random.default_rng(0).integers(2, g, size=n - 40).astype(np.int32)]))
    vals = np.where(gid == 0, 1e16, 1.0)
    got = seg_sum(jnp.asarray(vals), jnp.asarray(gid), g, sorted_gid=True)
    assert float(got[1]) == 20.0


# --- the batched integer sums (seg_sums): every column of a node in one pass,
# masked reductions up to bcast_segreduce_groups_max groups, one contraction above ---

_NBITS = (64, 1, 13, 64, 32, 24, 64)  # what a batch's columns declare


def _column(rng, n, nbits, k):
    """A column within its declared width; the 64-bit ones take negative
    values, and every other one of them values near +-2^63 whose sums wrap."""
    if nbits == 1:
        return rng.integers(0, 2, size=n).astype(np.bool_)
    if nbits < 64:
        return rng.integers(0, 1 << nbits, size=n, dtype=np.int64)
    if k % 2:
        return rng.integers(-1000, 1000, size=n, dtype=np.int64)
    edge = np.array([np.iinfo(np.int64).max, np.iinfo(np.int64).min,
                     np.iinfo(np.int64).max - 7, -(2**62)], np.int64)
    return edge[rng.integers(0, 4, size=n)] + rng.integers(
        -3, 3, size=n, dtype=np.int64)


def _batch(n, g, ncols, seed):
    rng = np.random.default_rng(seed)
    gid = rng.integers(0, g + 1, size=n).astype(np.int32)  # g == dead marker
    # out of range on both sides: none of these rows may count
    gid[:4] = (g + 5, np.iinfo(np.int32).max, -1, -7)
    cols = [(jnp.asarray(_column(rng, n, _NBITS[k], k)), _NBITS[k])
            for k in range(ncols)]
    return cols, jnp.asarray(gid)


def _scatter(vals, gid, g):
    """jax.ops.segment_sum, the rows outside [0, g) dropped (a negative id
    would count from the end there)."""
    dead = (gid < 0) | (gid >= g)
    with np.errstate(over="ignore"):
        return jax.ops.segment_sum(jnp.asarray(vals, jnp.int64),
                                   jnp.where(dead, g, gid), num_segments=g)


@pytest.mark.parametrize("ncols", [1, 3, 7])
@pytest.mark.parametrize("g", [2, 6, 64, 1024])
@pytest.mark.parametrize("n", [3 * 1024, 500])
def test_seg_sums_batch_matches_scatter(n, g, ncols):
    """rows: a multiple of 1,024 that is not one of 2,048, and one under 512
    (the old block rule sent both elsewhere); the first column is handed in
    twice and comes back twice."""
    cols, gid = _batch(n, g, ncols, seed=n + 7 * g + ncols)
    info = {}
    bits = [b for _, b in cols]
    got = jax.jit(lambda vs, i: seg_sums(
        list(zip(vs, bits)) + [(vs[0], bits[0])], i, g, info=info))(
        [v for v, _ in cols], gid)
    assert len(got) == ncols + 1
    for (vals, _), res in zip(cols + [cols[0]], got):
        assert res.dtype == jnp.int64
        np.testing.assert_array_equal(
            np.asarray(res), np.asarray(_scatter(vals, gid, g)))
    masked = g <= config.get("bcast_segreduce_groups_max")
    assert info == {
        "rows": n, "groups": g, "columns": ncols + 1, "distinct": ncols,
        "limbs": 0 if masked else sum(
            max(1, -(-b // 8)) for b in _NBITS[:ncols]),
        "formulation": "masked" if masked else "contract"}


@pytest.mark.parametrize("g", [6, 65])
def test_seg_sums_all_rows_dead(g):
    cols, gid = _batch(2048, g, 3, seed=g)
    for dead in (jnp.full_like(gid, g), jnp.full_like(gid, -1)):
        for res in seg_sums(cols, dead, g):
            np.testing.assert_array_equal(np.asarray(res), np.zeros(g))


@pytest.mark.parametrize("g", [6, 65])
def test_seg_sums_wrap_mod_2_64(g):
    """Two's-complement wrap, bit for bit: max + max + 2 in one group, min +
    min in another."""
    big, small = np.iinfo(np.int64).max, np.iinfo(np.int64).min
    vals = jnp.asarray(np.array([big, big, 2, small, small, 5], np.int64))
    gid = jnp.asarray(np.array([0, 0, 0, 1, 1, g - 1], np.int32))
    got, = seg_sums([(vals, 64)], gid, g)
    want = np.zeros(g, np.int64)
    want[0], want[1], want[g - 1] = 0, 0, 5
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(_scatter(vals, gid, g)))


def test_seg_sums_contraction_loops_over_row_blocks(monkeypatch):
    """Three whole steps of the contraction's loop and a tail that fills no
    block: the same sums."""
    monkeypatch.setattr(segment, "_CONTRACT_STEP_ROWS", 4096)
    monkeypatch.setattr(segment, "_CONTRACT_ROWS", 1024)
    n, g = 3 * 4096 + 1000, 70
    cols, gid = _batch(n, g, 4, seed=3)
    for (vals, _), res in zip(cols, seg_sums(cols, gid, g)):
        np.testing.assert_array_equal(
            np.asarray(res), np.asarray(_scatter(vals, gid, g)))


def test_seg_sums_floats_ride_along():
    """A float column in the batch keeps its own path and its place."""
    cols, gid = _batch(2048, 6, 2, seed=9)
    f = jnp.asarray(np.random.default_rng(2).normal(size=2048))
    info = {}
    got = seg_sums([cols[0], (f, 64), cols[1]], gid, 6, info=info)
    want = jax.ops.segment_sum(f, jnp.where((gid < 0) | (gid >= 6), 6, gid),
                               num_segments=6)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want),
                               rtol=1e-12)
    np.testing.assert_array_equal(
        np.asarray(got[2]), np.asarray(_scatter(cols[1][0], gid, 6)))
    assert (info["columns"], info["distinct"]) == (2, 2)
