"""Expression engine tests (reference analog: be/test/exprs/)."""

import datetime

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from starrocks_tpu import types as T
from starrocks_tpu.column import HostTable
from starrocks_tpu.exprs import (
    Case, Cast, Col, InList, Lit,
    add, and_, between, col, div, eq, eval_expr, eval_predicate, ge, gt,
    is_null, le, like, lit, lt, mul, ne, not_, or_, sub, year, month,
)
from starrocks_tpu.exprs.compile import like_to_regex
from starrocks_tpu.exprs.ir import Call, coalesce


def _chunk(**data):
    types = data.pop("__types", {})
    return HostTable.from_pydict(data, types=types).to_chunk()


def _vals(c, e, n):
    v = eval_expr(c, e)
    data = np.asarray(jnp.broadcast_to(v.data, (c.capacity,)))[:n]
    if v.valid is None:
        return list(data)
    valid = np.asarray(jnp.broadcast_to(v.valid, (c.capacity,)))[:n]
    return [d if ok else None for d, ok in zip(data, valid)]


def test_arithmetic_ints():
    c = _chunk(a=[1, 2, 3], b=[10, 20, 30])
    assert _vals(c, add(col("a"), col("b")), 3) == [11, 22, 33]
    assert _vals(c, mul(col("a"), lit(5)), 3) == [5, 10, 15]
    assert _vals(c, sub(col("b"), col("a")), 3) == [9, 18, 27]


def test_decimal_vs_float_literal_compares_as_integers():
    """`l_discount between 0.05 and 0.07` (TPC-H Q6): a DECIMAL column
    against a float literal compares as scaled integers — no float64 in the
    traced program. Casting the column to DOUBLE divides by 10^scale in
    float64, which a TPU emulates without correct rounding (a v5e answered
    Q6 28% low: every l_discount = 0.05 row failed `>= 0.05`)."""
    c = _chunk(disc=[0.04, 0.05, 0.06, 0.07, 0.08],
               __types={"disc": T.DECIMAL(15, 2)})
    e = between(col("disc"), lit(0.05), lit(0.07))
    jaxpr = jax.make_jaxpr(lambda ch: eval_expr(ch, e).data)(c)
    assert "f64" not in str(jaxpr)
    assert _vals(c, e, 5) == [False, True, True, True, False]
    # a literal finer than the column's scale widens the column instead
    assert _vals(c, lt(col("disc"), lit(0.055)), 5) == [
        True, True, False, False, False]
    # one with no exact decimal form keeps the DOUBLE comparison
    assert _vals(c, lt(col("disc"), lit(0.1 + 0.2)), 5) == [True] * 5


def test_divide_null_on_zero():
    c = _chunk(a=[10, 20, 30], b=[2, 0, 5])
    out = _vals(c, div(col("a"), col("b")), 3)
    assert out[0] == 5.0 and out[1] is None and out[2] == 6.0


def test_decimal_arithmetic():
    c = _chunk(
        price=[10.00, 20.50], disc=[0.05, 0.10],
        __types={"price": T.DECIMAL(15, 2), "disc": T.DECIMAL(15, 2)},
    )
    # price * (1 - disc): classic TPC-H Q1 expression
    e = mul(col("price"), sub(lit(1), col("disc")))
    v = eval_expr(c, e)
    assert v.type.is_decimal and v.type.scale == 4
    got = np.asarray(v.data)[:2]
    assert list(got) == [95000, 184500]  # 9.5000, 18.4500 at scale 4


def test_comparisons_and_null_prop():
    c = _chunk(a=[1, None, 3], b=[1, 2, 2])
    assert _vals(c, eq(col("a"), col("b")), 3) == [True, None, False]
    assert _vals(c, gt(col("a"), lit(2)), 3) == [False, None, True]
    # WHERE semantics: NULL -> excluded
    m = eval_predicate(c, gt(col("a"), lit(0)))
    assert list(np.asarray(m)[:3]) == [True, False, True]


def test_kleene_and_or():
    c = _chunk(a=[True, True, False, None], b=[None, True, None, None])
    assert _vals(c, and_(col("a"), col("b")), 4) == [None, True, False, None]
    assert _vals(c, or_(col("a"), col("b")), 4) == [True, True, None, None]


def test_is_null_not():
    c = _chunk(a=[1, None, 3])
    assert _vals(c, is_null(col("a")), 3) == [True if v is None else False for v in [1, None, 3]]
    assert _vals(c, not_(eq(col("a"), lit(1))), 3) == [False, None, True]


def test_case_when():
    c = _chunk(x=[1, 2, 3, 4])
    e = Case(
        whens=((lt(col("x"), lit(2)), lit(10)), (lt(col("x"), lit(4)), lit(20))),
        orelse=lit(30),
    )
    assert _vals(c, e, 4) == [10, 20, 20, 30]
    e2 = Case(whens=((eq(col("x"), lit(1)), lit(1)),), orelse=None)
    assert _vals(c, e2, 3) == [1, None, None]


def test_in_list():
    c = _chunk(s=["a", "b", "c", "d"], n=[1, 2, 3, 4])
    assert _vals(c, InList(col("s"), ("b", "d")), 4) == [False, True, False, True]
    assert _vals(c, InList(col("s"), ("zz",)), 4) == [False] * 4
    assert _vals(c, InList(col("n"), (2, 4), negated=True), 4) == [True, False, True, False]


def test_string_compare_and_like():
    c = _chunk(s=["apple", "banana", "cherry"])
    assert _vals(c, eq(col("s"), lit("banana")), 3) == [False, True, False]
    assert _vals(c, ne(col("s"), lit("banana")), 3) == [True, False, True]
    assert _vals(c, ge(col("s"), lit("banana")), 3) == [False, True, True]
    assert _vals(c, lt(col("s"), lit("b")), 3) == [True, False, False]
    assert _vals(c, like(col("s"), lit("%an%")), 3) == [False, True, False]
    assert _vals(c, like(col("s"), lit("_pple")), 3) == [True, False, False]


def test_like_regex_translation():
    assert like_to_regex("a%b_c") == "^a.*b.c$"
    assert like_to_regex("100\\%") == "^100%$"


def test_dates():
    c = HostTable.from_pydict(
        {"d": [
            (datetime.date(1998, 9, 2) - datetime.date(1970, 1, 1)).days,
            (datetime.date(1995, 1, 15) - datetime.date(1970, 1, 1)).days,
        ]},
        types={"d": T.DATE},
    ).to_chunk()
    assert _vals(c, year(col("d")), 2) == [1998, 1995]
    assert _vals(c, month(col("d")), 2) == [9, 1]
    assert _vals(c, le(col("d"), lit("1998-09-02")), 2) == [True, True]
    assert _vals(c, lt(col("d"), lit("1995-01-15")), 2) == [False, False]
    assert _vals(c, between(col("d"), lit("1995-01-01"), lit("1996-01-01")), 2) == [False, True]


def test_civil_from_days_vs_numpy():
    from starrocks_tpu.exprs.compile import _civil_from_days

    days = np.arange(-3000, 40000, 370)
    y, m, d = _civil_from_days(jnp.asarray(days))
    dates = days.astype("datetime64[D]")
    ys = dates.astype("datetime64[Y]").astype(int) + 1970
    ms = dates.astype("datetime64[M]").astype(int) % 12 + 1
    np.testing.assert_array_equal(np.asarray(y), ys)
    np.testing.assert_array_equal(np.asarray(m), ms)


def test_string_map_fns():
    c = _chunk(s=["Apple", "BANANA"])
    from starrocks_tpu.exprs.ir import Call

    up = eval_expr(c, Call("upper", col("s")))
    assert list(up.dict.decode(np.asarray(up.data)[:2])) == ["APPLE", "BANANA"]
    sb = eval_expr(c, Call("substr", col("s"), lit(1), lit(3)))
    assert list(sb.dict.decode(np.asarray(sb.data)[:2])) == ["App", "BAN"]


def test_coalesce():
    c = _chunk(a=[1, None, None], b=[None, 5, None])
    assert _vals(c, Call("coalesce", col("a"), col("b"), lit(0)), 3) == [1, 5, 0]


def test_cast():
    c = _chunk(a=[1, 2])
    v = eval_expr(c, Cast(col("a"), T.DOUBLE))
    assert v.type == T.DOUBLE
    v2 = eval_expr(c, Cast(col("a"), T.DECIMAL(15, 2)))
    assert list(np.asarray(v2.data)[:2]) == [100, 200]


def test_exprs_jittable():
    c = _chunk(a=[1.0, 2.0, 3.0], b=[4.0, 5.0, 6.0])

    @jax.jit
    def run(ch):
        return eval_predicate(ch, gt(add(col("a"), col("b")), lit(6.5)))

    m = run(c)
    assert list(np.asarray(m)[:3]) == [False, True, True]
    run(c)
    assert run._cache_size() == 1


def test_datetime_end_to_end():
    import tempfile

    from starrocks_tpu.runtime.session import Session

    d = tempfile.mkdtemp()
    s = Session(data_dir=d)
    s.sql("create table ev (id int, ts datetime, v double)")
    s.sql("""insert into ev values (1, '2024-03-01 10:30:00', 1.5),
             (2, '2024-03-01 11:00:00', 2.5), (3, '2024-03-02 09:00:00', 4.0)""")
    assert s.sql("select id from ev where ts >= '2024-03-01 11:00:00' order by id").rows() == [(2,), (3,)]
    assert s.sql("select id from ev where ts < '2024-03-02' order by id").rows() == [(1,), (2,)]
    assert s.sql("select day(ts) d, sum(v) s from ev group by 1 order by 1").rows() == [(1, 4.0), (2, 4.0)]
    # real parquet persistence roundtrip (fresh session over the same dir)
    s2 = Session(data_dir=d)
    assert s2.sql("select id from ev where ts >= '2024-03-01 11:00' order by id").rows() == [(2,), (3,)]
    # string comparisons with datetime-looking literals stay string-typed
    s2.sql("create table sv (name varchar)")
    s2.sql("insert into sv values ('2024-03-01 11:00:00'), ('other')")
    assert s2.sql("select count(*) c from sv where name = '2024-03-01 11:00:00'").rows() == [(1,)]
    # garbage time values in string context stay plain strings
    assert s2.sql("select count(*) c from sv where name = '2024-03-01 99:99'").rows() == [(0,)]
    # IN-list on a datetime column
    assert s2.sql("select id from ev where ts in ('2024-03-01 10:30:00')").rows() == [(1,)]
