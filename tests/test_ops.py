"""Operator tests with pandas as differential oracle
(reference analog: be/test/exec/ operator unit tests)."""

import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp

from starrocks_tpu import types as T
from starrocks_tpu.column import HostTable
from starrocks_tpu.exprs import AggExpr, col, gt, lit, mul
from starrocks_tpu.ops import (
    COMPLETE, FINAL, PARTIAL,
    INNER, LEFT_ANTI, LEFT_OUTER, LEFT_SEMI,
    compact, filter_chunk, final_agg_exprs, hash_aggregate,
    hash_join_expand, hash_join_unique, limit_chunk, project, sort_chunk,
)


def _res(chunk):
    return HostTable.from_chunk(chunk).to_pylist()


def test_filter_project():
    c = HostTable.from_pydict({"a": [1, 2, 3, 4], "b": [10.0, 20.0, 30.0, 40.0]}).to_chunk()
    f = filter_chunk(c, gt(col("a"), lit(2)))
    assert int(f.num_rows()) == 2
    p = project(f, [mul(col("b"), lit(2.0))], ["b2"])
    assert _res(p) == [(60.0,), (80.0,)]


def test_compact():
    c = HostTable.from_pydict({"a": list(range(10))}).to_chunk()
    f = filter_chunk(c, gt(col("a"), lit(6)))
    k, kn = compact(f)
    assert int(kn) == 3
    arr = np.asarray(k.col("a")[0])
    assert list(arr[:3]) == [7, 8, 9]
    assert int(k.num_rows()) == 3


def _compact_case(cap, share, seed):
    """A chunk of every column kind `compact` moves, one of them nullable,
    and its live mask."""
    from starrocks_tpu.column.column import Chunk, Field, Schema

    rng = np.random.default_rng(seed)
    live = (rng.random(cap) < share if 0.0 < share < 1.0
            else np.full(cap, share >= 1.0))
    cols = {
        "i64": (T.BIGINT, rng.integers(-(1 << 62), 1 << 62, cap), None),
        "i32": (T.INT, rng.integers(-(1 << 31), 1 << 31, cap)
                .astype(np.int32), rng.random(cap) < 0.7),
        "f64": (T.DOUBLE, rng.normal(size=cap), None),
        "b": (T.BOOLEAN, rng.random(cap) < 0.5, None),
    }
    chunk = Chunk(
        Schema(tuple(Field(n, t, v is not None)
                     for n, (t, _, v) in cols.items())),
        tuple(jnp.asarray(d) for _, d, _ in cols.values()),
        tuple(None if v is None else jnp.asarray(v)
              for _, _, v in cols.values()),
        jnp.asarray(live))
    return chunk, cols, live


# (capacity, live share, out capacity as a share of the capacity or None):
# 0%, 1%, 61% and 100% live; the output capacity below, at and above the
# live count; capacities that are and are not powers of two, 10 rows to 2^17
@pytest.mark.parametrize("cap,share,out", [
    (10, 0.61, None), (1000, 0.0, None), (1000, 1.0, None),
    (1000, 1.0, 0.5), (1024, 0.61, 0.25), (8192, 0.01, 0.125),
    (8192, 0.61, 0.75), (8192, 0.61, 0.5), (9216, 0.0, 0.25),
    (9216, 1.0, 1.0), (61440, 0.01, 0.05), (61440, 0.61, 0.61),
    (131072, 0.61, 0.7), (131072, 0.01, 0.0078125), (1000, 0.61, 2.0),
])
def test_compact_matches_numpy(cap, share, out):
    chunk, cols, live = _compact_case(cap, share, seed=cap + int(share * 100))
    out_cap = cap if out is None else int(cap * out)
    got, n = jax.jit(compact, static_argnums=1)(
        chunk, None if out is None else out_cap)
    rows = np.flatnonzero(live)
    assert int(n) == len(rows)  # the true count, whatever was dropped
    assert got.capacity == out_cap
    rows = rows[:out_cap]
    np.testing.assert_array_equal(
        np.asarray(got.sel), np.arange(out_cap) < len(rows))
    for (name, (_, d, v)), gd, gv in zip(cols.items(), got.data, got.valid):
        want = np.zeros(out_cap, d.dtype)  # zeros / False past the rows
        want[:len(rows)] = d[rows]
        np.testing.assert_array_equal(np.asarray(gd), want, err_msg=name)
        assert (gv is None) == (v is None)
        if v is not None:
            wv = np.zeros(out_cap, bool)
            wv[:len(rows)] = v[rows]
            np.testing.assert_array_equal(np.asarray(gv), wv, err_msg=name)


def test_aggregate_basic_vs_pandas():
    rng = np.random.default_rng(0)
    n = 5000
    df = pd.DataFrame({
        "k1": rng.integers(0, 7, n),
        "k2": rng.integers(0, 3, n),
        "v": rng.normal(size=n),
        "w": rng.integers(0, 100, n),
    })
    c = HostTable.from_pydict({k: df[k].to_numpy() for k in df}).to_chunk()
    out, ng = hash_aggregate(
        c,
        group_by=(("k1", col("k1")), ("k2", col("k2"))),
        aggs=(
            ("s", AggExpr("sum", col("v"))),
            ("cnt", AggExpr("count", None)),
            ("mn", AggExpr("min", col("w"))),
            ("mx", AggExpr("max", col("w"))),
            ("av", AggExpr("avg", col("v"))),
        ),
        num_groups=64,
    )
    assert int(ng) == 21
    got = pd.DataFrame(
        _res(out), columns=["k1", "k2", "s", "cnt", "mn", "mx", "av"]
    ).sort_values(["k1", "k2"]).reset_index(drop=True)
    exp = (
        df.groupby(["k1", "k2"], as_index=False)
        .agg(s=("v", "sum"), cnt=("v", "size"), mn=("w", "min"), mx=("w", "max"), av=("v", "mean"))
        .sort_values(["k1", "k2"]).reset_index(drop=True)
    )
    np.testing.assert_allclose(got["s"], exp["s"], rtol=1e-9)
    np.testing.assert_array_equal(got["cnt"], exp["cnt"])
    np.testing.assert_array_equal(got["mn"], exp["mn"])
    np.testing.assert_array_equal(got["mx"], exp["mx"])
    np.testing.assert_allclose(got["av"], exp["av"], rtol=1e-9)


def test_aggregate_nulls_and_dead_rows():
    c = HostTable.from_pydict(
        {"k": [1, 1, 2, 2, 2], "v": [1.0, None, 3.0, None, 5.0]}
    ).to_chunk()
    c = filter_chunk(c, gt(col("k"), lit(0)))  # all live; then kill row 4
    c = c.and_sel(jnp.arange(c.capacity) != 4)
    out, ng = hash_aggregate(
        c, (("k", col("k")),),
        (("s", AggExpr("sum", col("v"))), ("c", AggExpr("count", col("v"))),
         ("cs", AggExpr("count", None))),
        num_groups=8,
    )
    rows = sorted(_res(out))
    assert int(ng) == 2
    assert rows == [(1, 1.0, 1, 2), (2, 3.0, 1, 2)]


def test_aggregate_null_group_key():
    c = HostTable.from_pydict({"k": [1, None, None, 2], "v": [1, 2, 3, 4]}).to_chunk()
    out, ng = hash_aggregate(
        c, (("k", col("k")),), (("s", AggExpr("sum", col("v"))),), num_groups=8
    )
    assert int(ng) == 3
    rows = _res(out)
    bynull = {r[0]: r[1] for r in rows}
    assert bynull[None] == 5 and bynull[1] == 1 and bynull[2] == 4


def test_global_aggregate_empty_input():
    c = HostTable.from_pydict({"v": [1.0, 2.0]}).to_chunk()
    c = c.and_sel(jnp.zeros((c.capacity,), jnp.bool_))
    out, ng = hash_aggregate(
        c, (), (("c", AggExpr("count", None)), ("s", AggExpr("sum", col("v")))),
        num_groups=1,
    )
    rows = _res(out)
    assert rows == [(0, None)]  # COUNT=0, SUM=NULL over empty set


def test_two_phase_aggregate():
    rng = np.random.default_rng(1)
    n = 2000
    k = rng.integers(0, 5, n)
    v = rng.normal(size=n)
    full = HostTable.from_pydict({"k": k, "v": v}).to_chunk()
    aggs = (("s", AggExpr("sum", col("v"))), ("a", AggExpr("avg", col("v"))),
            ("c", AggExpr("count", None)))
    # single phase
    ref, _ = hash_aggregate(full, (("k", col("k")),), aggs, num_groups=8)
    # two phase: split rows in half, partial each, concat states, final
    h1 = HostTable.from_pydict({"k": k[:1000], "v": v[:1000]}).to_chunk()
    h2 = HostTable.from_pydict({"k": k[1000:], "v": v[1000:]}).to_chunk()
    p1, _ = hash_aggregate(h1, (("k", col("k")),), aggs, num_groups=8, mode=PARTIAL)
    p2, _ = hash_aggregate(h2, (("k", col("k")),), aggs, num_groups=8, mode=PARTIAL)
    # concat the two partial chunks host-side (exchange analog)
    t1, t2 = HostTable.from_chunk(p1), HostTable.from_chunk(p2)
    merged = HostTable(
        t1.schema,
        {f.name: np.concatenate([t1.arrays[f.name], t2.arrays[f.name]]) for f in t1.schema},
        {k2: np.concatenate([t1.valids[k2], t2.valids[k2]]) for k2 in t1.valids},
    ).to_chunk()
    fin, _ = hash_aggregate(
        merged, (("k", col("k")),), final_agg_exprs(aggs), num_groups=8, mode=FINAL
    )
    a = sorted(_res(ref))
    b = sorted(_res(fin))
    for ra, rb in zip(a, b):
        np.testing.assert_allclose(ra, rb, rtol=1e-9)


def _join_inputs():
    probe = HostTable.from_pydict(
        {"pk": [1, 2, 3, 4, 5], "pv": [10, 20, 30, 40, 50]}
    ).to_chunk()
    build = HostTable.from_pydict(
        {"bk": [2, 4, 6], "bv": ["x", "y", "z"]}
    ).to_chunk()
    return probe, build


def test_join_unique_inner():
    probe, build = _join_inputs()
    out = hash_join_unique(probe, build, (col("pk"),), (col("bk"),), INNER,
                           payload=["bv"])
    assert sorted(_res(out)) == [(2, 20, "x"), (4, 40, "y")]


def test_join_unique_left_outer():
    probe, build = _join_inputs()
    out = hash_join_unique(probe, build, (col("pk"),), (col("bk"),), LEFT_OUTER,
                           payload=["bv"])
    rows = sorted(_res(out))
    assert rows == [(1, 10, None), (2, 20, "x"), (3, 30, None), (4, 40, "y"), (5, 50, None)]


def test_join_semi_anti():
    probe, build = _join_inputs()
    semi = hash_join_unique(probe, build, (col("pk"),), (col("bk"),), LEFT_SEMI)
    assert sorted(r[0] for r in _res(semi)) == [2, 4]
    anti = hash_join_unique(probe, build, (col("pk"),), (col("bk"),), LEFT_ANTI)
    assert sorted(r[0] for r in _res(anti)) == [1, 3, 5]


def test_join_null_keys_never_match():
    probe = HostTable.from_pydict({"pk": [1, None, 3]}).to_chunk()
    build = HostTable.from_pydict({"bk": [None, 3], "bv": [7, 8]}).to_chunk()
    out = hash_join_unique(probe, build, (col("pk"),), (col("bk"),), INNER,
                           payload=["bv"])
    assert _res(out) == [(3, 8)]
    lo = hash_join_unique(probe, build, (col("pk"),), (col("bk"),), LEFT_OUTER,
                          payload=["bv"])
    assert sorted(_res(lo), key=str) == sorted([(1, None), (None, None), (3, 8)], key=str)


def test_join_expand_duplicates_vs_pandas():
    rng = np.random.default_rng(2)
    pdf = pd.DataFrame({"k": rng.integers(0, 10, 200), "pv": np.arange(200)})
    bdf = pd.DataFrame({"k": rng.integers(0, 10, 30), "bv": np.arange(30) * 10})
    probe = HostTable.from_pydict({"pk": pdf["k"].to_numpy(), "pv": pdf["pv"].to_numpy()}).to_chunk()
    build = HostTable.from_pydict({"bk": bdf["k"].to_numpy(), "bv": bdf["bv"].to_numpy()}).to_chunk()
    out, total = hash_join_expand(
        probe, build, (col("pk"),), (col("bk"),), out_capacity=2048, join_type=INNER,
        payload=["bv"],
    )
    exp = pdf.merge(bdf, on="k")
    assert int(total) == len(exp)
    got = sorted(_res(out))
    expected = sorted(zip(exp["k"], exp["pv"], exp["bv"]))
    assert got == [tuple(map(int, e)) for e in expected]


def test_join_expand_left_outer():
    probe = HostTable.from_pydict({"pk": [1, 2, 2, 9]}).to_chunk()
    build = HostTable.from_pydict({"bk": [2, 2, 3], "bv": [5, 6, 7]}).to_chunk()
    out, total = hash_join_expand(
        probe, build, (col("pk"),), (col("bk"),), out_capacity=1024,
        join_type=LEFT_OUTER, payload=["bv"],
    )
    rows = sorted(_res(out), key=str)
    assert (1, None) in rows and (9, None) in rows
    assert (2, 5) in rows and (2, 6) in rows
    assert int(total) == 6  # 1,9 -> 1 row each; each 2 -> 2 rows


def test_multi_key_join_packed():
    probe = HostTable.from_pydict({"a": [1, 1, 2], "b": [5, 6, 5], "v": [1, 2, 3]}).to_chunk()
    build = HostTable.from_pydict({"x": [1, 2], "y": [6, 5], "w": [100, 200]}).to_chunk()
    out = hash_join_unique(
        probe, build, (col("a"), col("b")), (col("x"), col("y")), INNER,
        payload=["w"], bit_widths=(20, 20),
    )
    assert sorted(_res(out)) == [(1, 6, 2, 100), (2, 5, 3, 200)]


def test_sort_and_limit():
    c = HostTable.from_pydict(
        {"a": [3, 1, None, 2], "b": [1.0, 2.0, 3.0, 4.0]}
    ).to_chunk()
    s = sort_chunk(c, ((col("a"), True, False),))  # asc, nulls last
    rows = _res(s)
    assert [r[0] for r in rows] == [1, 2, 3, None]
    s2 = sort_chunk(c, ((col("a"), False, True),))  # desc, nulls first
    assert [r[0] for r in _res(s2)] == [None, 3, 2, 1]
    s3 = sort_chunk(c, ((col("a"), True, False),), limit=2)
    assert [r[0] for r in _res(s3)] == [1, 2]
    l = limit_chunk(c, 2, offset=1)
    assert [r[0] for r in _res(l)] == [1, None]


def test_sort_multi_key_vs_pandas():
    rng = np.random.default_rng(3)
    df = pd.DataFrame({
        "a": rng.integers(0, 4, 100),
        "b": rng.normal(size=100),
    })
    c = HostTable.from_pydict({k: df[k].to_numpy() for k in df}).to_chunk()
    s = sort_chunk(c, ((col("a"), True, False), (col("b"), False, False)))
    got = pd.DataFrame(_res(s), columns=["a", "b"])
    exp = df.sort_values(["a", "b"], ascending=[True, False]).reset_index(drop=True)
    np.testing.assert_array_equal(got["a"], exp["a"])
    np.testing.assert_allclose(got["b"], exp["b"])


def test_aggregate_jit_composable():
    c = HostTable.from_pydict({"k": [1, 2, 1], "v": [1.0, 2.0, 3.0]}).to_chunk()

    @jax.jit
    def q(ch):
        f = filter_chunk(ch, gt(col("v"), lit(0.5)))
        out, ng = hash_aggregate(
            f, (("k", col("k")),), (("s", AggExpr("sum", col("v"))),), num_groups=8
        )
        return out, ng

    out, ng = q(c)
    assert int(ng) == 2
    assert sorted(_res(out)) == [(1, 4.0), (2, 2.0)]


def test_join_expand_null_probe_key_left_outer():
    # regression: NULL-key probe rows must not match the build sentinel run
    probe = HostTable.from_pydict({"pk": [None, 2]}).to_chunk()
    build = HostTable.from_pydict({"bk": [None, 2], "bv": [999, 5]}).to_chunk()
    out, total = hash_join_expand(
        probe, build, (col("pk"),), (col("bk"),), out_capacity=1024,
        join_type=LEFT_OUTER, payload=["bv"],
    )
    rows = sorted(_res(out), key=str)
    assert (None, None) in rows and (2, 5) in rows
    assert (None, 999) not in rows


def test_dense_runtime_filter_exactness():
    # an exact IN-set filter passes ONLY surviving build keys (min/max can't)
    from starrocks_tpu.ops.join import runtime_filter_mask

    probe = HostTable.from_pydict({"pk": [1, 2, 3, 4, 5, 6]}).to_chunk()
    build = HostTable.from_pydict({"bk": [1, 3, 5, 6]}).to_chunk()
    build = build.and_sel(jnp.asarray(
        [True, True, False, True] + [False] * (build.capacity - 4)))  # drop 5
    m = runtime_filter_mask(probe, build, (col("pk"),), (col("bk"),),
                            dense_range=(1, 6))
    assert list(np.asarray(m)[:6]) == [True, False, True, False, False, True]
    # min/max only bounds the range
    m2 = runtime_filter_mask(probe, build, (col("pk"),), (col("bk"),))
    assert list(np.asarray(m2)[:6]) == [True, True, True, True, True, True]


def test_stale_stats_program_eviction():
    # regression: INSERT must evict cached programs whose traces baked
    # stats-derived constants (dense RF ranges)
    from starrocks_tpu.runtime.session import Session

    s = Session()
    s.sql("create table dl (k int)")
    s.sql("create table dr (k int, v int)")
    s.sql("insert into dl values (1), (2)")
    s.sql("insert into dr values (1, 10), (2, 20)")
    q = "select dl.k, dr.v from dl, dr where dl.k = dr.k order by 1"
    assert s.sql(q).rows() == [(1, 10), (2, 20)]
    # extend the key range WITHOUT changing padded capacities
    s.sql("insert into dl values (99)")
    s.sql("insert into dr values (99, 990)")
    assert s.sql(q).rows() == [(1, 10), (2, 20), (99, 990)]
