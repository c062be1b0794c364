"""Hash placement on a mesh (`DeviceCache.chunk_for` with a ("hash", key)
mode) derives a table's shard layout — hash the key column, count the rows a
shard, sort the rows by shard — only when the capacity, a column or the
selection mask it feeds is not cached yet, on four virtual CPU devices with
small hash-distributed `lineitem` and `orders`: a warm statement derives
nothing, a column placed later lands in the rows of the ones placed before,
DML makes the next statement derive again, and the narrow bucket sort is the
int32 one."""

import numpy as np
import pytest

import starrocks_tpu.sql.distributed as D
from starrocks_tpu.runtime.executor import DeviceCache, hash_shard_layout
from starrocks_tpu.runtime.metrics import HASH_LAYOUTS, HASH_PLACEMENTS
from starrocks_tpu.runtime.session import Session

N = 4
JOIN = ("select o_custkey, sum(l_quantity) q, count(*) c, sum(l_price) p "
        "from lineitem, orders where l_orderkey = o_orderkey "
        "group by o_custkey order by o_custkey")


@pytest.fixture(scope="module")
def small_tables_shard(eight_devices):
    """Tables of a few thousand rows shard (by hash of their distribution
    column) as SF10's do."""
    old = D.SHARD_THRESHOLD_ROWS
    D.SHARD_THRESHOLD_ROWS = 1_000
    yield
    D.SHARD_THRESHOLD_ROWS = old


def _session(small_tables_shard) -> Session:
    s = Session(dist_shards=N)
    s.sql("create table lineitem (l_orderkey bigint not null, "
          "l_quantity bigint, l_price double, l_tax bigint) "
          "distributed by hash(l_orderkey) buckets 4")
    s.sql("create table orders (o_orderkey bigint not null, o_custkey bigint) "
          "distributed by hash(o_orderkey) buckets 4")
    rng = np.random.default_rng(11)
    s.sql("insert into orders values "
          + ",".join(f"({i},{i % 37})" for i in range(1500)))
    keys = rng.integers(-200, 1500, size=4000)
    s.sql("insert into lineitem values " + ",".join(
        f"({k},{i % 50},{i * 0.25},{i % 7})" for i, k in enumerate(keys)))
    return s


def _counted(fn):
    """fn's result, and the placements and layouts it counted."""
    p0, l0 = HASH_PLACEMENTS.value, HASH_LAYOUTS.value
    out = fn()
    return out, HASH_PLACEMENTS.value - p0, HASH_LAYOUTS.value - l0


def _spans(profile) -> list:
    out = [n for n, _, _ in profile.spans]
    for child in profile.children:
        out += _spans(child)
    return out


def _hash_placed(s: Session, table: str, columns: tuple, cache=None):
    """`columns` of `table` as the session's mesh places them by hash of
    their distribution column, from `cache` (the session's by default)."""
    de = s._dist_executor
    handle = s.catalog.get_table(table)
    mode = ("hash", f"{table}.{handle.distribution[0]}")
    return (cache or s.cache).chunk_for(
        handle, table, columns, placement=(de.mesh, de.axis, mode))


def _host(chunk) -> list:
    return [None if a is None else np.asarray(a)
            for a in (*chunk.data, *chunk.valid, chunk.sel)]


def test_warm_statement_derives_no_layout(small_tables_shard):
    s = _session(small_tables_shard)
    first, placed, derived = _counted(lambda: s.sql(JOIN))
    assert derived == 2  # lineitem's and orders', once each
    assert placed >= 2
    assert "hash_layout" in _spans(s.last_profile)
    for _ in range(2):
        again, placed, derived = _counted(lambda: s.sql(JOIN))
        attempts = [c for c in s.last_profile.children
                    if c.name.startswith("attempt_")]
        assert derived == 0
        assert placed == 2 * len(attempts) > 0  # two scans an attempt
        assert "hash_layout" not in _spans(s.last_profile)
        assert again.rows() == first.rows()
    assert first.rows() == Session(s.catalog).sql(JOIN).rows()


def test_later_column_lands_in_the_same_rows(small_tables_shard):
    s = _session(small_tables_shard)
    s.sql(JOIN)
    # l_tax is first placed here: lineitem's layout is derived again for
    # it, orders' not at all
    later = "select sum(l_tax) t from lineitem where l_orderkey % 3 = 1"
    r, _, derived = _counted(lambda: s.sql(later))
    assert derived == 1
    assert r.rows() == Session(s.catalog).sql(later).rows()
    cols = ("l_orderkey", "l_quantity", "l_price", "l_tax")
    cached, _, derived = _counted(
        lambda: _hash_placed(s, "lineitem", cols))
    assert derived == 0
    fresh, _, derived = _counted(
        lambda: _hash_placed(s, "lineitem", cols, DeviceCache()))
    assert derived == 1
    for got, want in zip(_host(cached), _host(fresh)):
        if want is None:
            assert got is None
        else:
            np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("dml", [
    "insert into lineitem values (5, 7, 1.5, 2), (-3, 9, 2.5, 1)",
    "delete from lineitem where l_quantity = 3",
])
def test_dml_derives_the_layout_again(small_tables_shard, dml):
    s = _session(small_tables_shard)
    s.sql(JOIN)
    s.sql(JOIN)
    s.sql(dml)
    r, placed, derived = _counted(lambda: s.sql(JOIN))
    assert derived == 1  # lineitem's; orders' columns are still cached
    assert placed >= 2
    assert r.rows() == Session(s.catalog, dist_shards=N).sql(JOIN).rows()
    assert r.rows() == Session(s.catalog).sql(JOIN).rows()
    _, _, derived = _counted(lambda: s.sql(JOIN))
    assert derived == 0


@pytest.mark.parametrize("n_shards", [2, 4, 5, 8, 13, 256, 257, 1000])
def test_narrow_bucket_sort_is_the_int32_sort(n_shards):
    from starrocks_tpu.native import hash_partition_i64

    rng = np.random.default_rng(n_shards)
    keys = np.concatenate([rng.integers(-2**62, 2**62, size=20_000),
                           rng.integers(0, 50, size=5_000)])
    counts, order = hash_shard_layout(keys, n_shards)
    bucket = hash_partition_i64(keys, n_shards)
    assert bucket.dtype == np.int32
    np.testing.assert_array_equal(order, np.argsort(bucket, kind="stable"))
    np.testing.assert_array_equal(counts,
                                  np.bincount(bucket, minlength=n_shards))
    assert np.all(np.diff(bucket[order]) >= 0)
