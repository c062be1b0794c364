"""Low-cardinality (sort-free) aggregation fast path tests."""

import pytest

from starrocks_tpu.runtime.config import config
from starrocks_tpu.runtime.session import Session
from starrocks_tpu.storage.catalog import tpch_catalog


QUERIES = [
    # dict keys, no NULLs
    """select l_returnflag, l_linestatus, sum(l_quantity) q,
       sum(l_extendedprice) p, avg(l_discount) a, count(*) c,
       min(l_extendedprice) mn, max(l_extendedprice) mx
       from lineitem where l_shipdate <= date '1998-09-02'
       group by l_returnflag, l_linestatus order by 1, 2""",
    # boolean-derived key mixes with dict key via CASE? (bool col via expr)
    """select l_returnflag, count(*) c from lineitem
       group by l_returnflag order by 1""",
]


def _same_rows(got, want, rel):
    assert len(got) == len(want)
    for gr, wr in zip(got, want):
        for gv, wv in zip(gr, wr):
            if isinstance(wv, float):
                assert gv == pytest.approx(wv, rel=rel, abs=1e-12)
            else:
                assert gv == wv


@pytest.fixture(scope="module")
def cat():
    return tpch_catalog(sf=0.01)


@pytest.mark.parametrize("qi", range(len(QUERIES)))
def test_lowcard_matches_sort_path(cat, qi):
    q = QUERIES[qi]
    fast = Session(cat).sql(q).rows()
    config.set("enable_lowcard_agg", False)
    try:
        slow = Session(cat).sql(q).rows()
    finally:
        config.set("enable_lowcard_agg", True)
    _same_rows(slow, fast, rel=1e-12)


def test_lowcard_with_nulls_and_two_phase():
    s = Session()
    s.sql("create table t (g varchar, v double)")
    s.sql("insert into t values ('a', 1.0), (null, 2.0), ('a', null), ('b', 4.0), (null, 6.0)")
    q = "select g, count(*) c, count(v) cv, sum(v) s, avg(v) a from t group by g order by g nulls last"
    fast = s.sql(q).rows()
    config.set("enable_lowcard_agg", False)
    try:
        slow = Session(s.catalog).sql(q).rows()
    finally:
        config.set("enable_lowcard_agg", True)
    # the two paths reduce in different row orders; float sums may differ in
    # the last ulp (esp. on TPU)
    _same_rows(slow, fast, rel=1e-12)
    assert fast[-1][0] is None and fast[-1][1] == 2  # NULL group


def test_lowcard_distributed_two_phase(eight_devices, cat):
    import starrocks_tpu.sql.distributed as D

    old = D.SHARD_THRESHOLD_ROWS
    D.SHARD_THRESHOLD_ROWS = 10_000
    try:
        q = QUERIES[0]
        single = Session(cat).sql(q).rows()
        dist = Session(cat, dist_shards=8).sql(q).rows()
        assert single == dist
    finally:
        D.SHARD_THRESHOLD_ROWS = old


# every integer sum of a node is one `seg_sums` batch (PR 27): Q1 whole, and
# the moment aggregates, whose counts ride with their float sums
BATCHED = {
    "q1": """select l_returnflag, l_linestatus, sum(l_quantity) sum_qty,
       sum(l_extendedprice) sum_base_price,
       sum(l_extendedprice * (1 - l_discount)) sum_disc_price,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) sum_charge,
       avg(l_quantity) avg_qty, avg(l_extendedprice) avg_price,
       avg(l_discount) avg_disc, count(*) count_order
       from lineitem where l_shipdate <= date '1998-09-02'
       group by l_returnflag, l_linestatus order by 1, 2""",
    "moments": """select l_returnflag, l_linestatus, var_pop(l_quantity) vp,
       stddev_samp(l_extendedprice) sd, sum(l_quantity) sq,
       covar_samp(l_quantity, l_extendedprice) cv,
       corr(l_quantity, l_discount) cr, count(l_tax) ct, count(*) c
       from lineitem group by l_returnflag, l_linestatus order by 1, 2""",
}


@pytest.mark.parametrize("name", sorted(BATCHED))
def test_batched_sums_match_sort_path_and_mesh(eight_devices, cat, name):
    """The packed-gid path (6 groups, the
    masked reductions) against the sort path (capacity 1,024, the
    contraction) and against PARTIAL a shard + FINAL on eight virtual
    devices; integer and DECIMAL results to the digit."""
    import starrocks_tpu.sql.distributed as D

    q = BATCHED[name]
    old = D.SHARD_THRESHOLD_ROWS
    D.SHARD_THRESHOLD_ROWS = 10_000
    try:
        r = Session(cat).sql(q)
        fast = r.rows()
        took = [a.infos["segment_sums"] for a in r.profile.children
                if "segment_sums" in a.infos][-1]
        assert {t["formulation"] for t in took.values()} == {"masked"}
        config.set("enable_lowcard_agg", False)
        try:
            r = Session(cat).sql(q)
            slow = r.rows()
            took = [a.infos["segment_sums"] for a in r.profile.children
                    if "segment_sums" in a.infos][-1]
            assert {t["formulation"] for t in took.values()} == {"contract"}
        finally:
            config.set("enable_lowcard_agg", True)
        dist = Session(cat, dist_shards=8).sql(q).rows()
    finally:
        D.SHARD_THRESHOLD_ROWS = old
    _same_rows(slow, fast, rel=1e-12)
    _same_rows(dist, fast, rel=1e-9)
