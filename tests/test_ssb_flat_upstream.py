"""SSB-flat as upstream publishes it (`benchmarks/configs/
ssb_flat_sf100_share.json`): the benchmark's own 13 statements, in upstream's
text (alias `year`, lower-case names against a DDL in capitals, DATE against
string literals, `year()` / `weekofyear()` of the DATE column), against the
benchmark's own pandas references on a generated share of ~300,000 rows whose
filters all select rows, integers compared for equality; the parser and
analyzer rules that text needs; the compaction counters and the date-part
scope the flat-table cell's metrics read. `tests/test_ssb_sql.py` keeps the
repo's earlier formulation (pre-extracted year columns, DECIMAL prices)."""

import os

import pytest

from benchmarks.harness import cells, compare
from starrocks_tpu.runtime.metrics import metrics
from starrocks_tpu.runtime.session import Session
from starrocks_tpu.sql.analyzer import AnalyzerError
from starrocks_tpu.sql.parser import ParseError, parse
from starrocks_tpu.storage.catalog import Catalog

from lowering import SCOPED, lowered_text

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "ssb_flat_sf100_share.cycle13x10"
CELL = cells.Cell(ROOT, NAME)
# a share on which every one of the 13 filters selects rows: Q3.4's (two
# cities on both sides, one month) keeps 8 rows in 10 million
SF, SEED = 0.4, 10


@pytest.fixture(scope="module")
def share():
    gen = cells.load_module(ROOT, "datagen", CELL.config["generator"])
    tables = gen.generate(SF, SEED)
    catalog = Catalog()
    for name, table in tables.items():
        catalog.register(name, table, gen.UNIQUE_KEYS.get(name, ()),
                         gen.DISTRIBUTION.get(name, ()))
    return tables, Session(catalog)


@pytest.fixture(scope="module")
def frames(share):
    return compare.frames(share[0], compare.union_columns(
        [t["oracle"].COLUMNS for t in CELL.templates]))


def test_the_cell_sends_upstreams_13_in_upstreams_order():
    assert [v["template"] for v in CELL.variants] == [
        f"ssb_flat.q{a}.{b}" for a, n in ((1, 3), (2, 3), (3, 4), (4, 3))
        for b in range(1, n + 1)]
    assert CELL.traffic["min_cycles"] >= 10 and CELL.traffic["clients"] == 1
    assert CELL.config["reduced"] == ["rows_held"]


@pytest.mark.parametrize("variant", CELL.variants,
                         ids=[v["template"] for v in CELL.variants])
def test_statement_equals_its_reference_exactly(share, frames, variant):
    _, session = share
    got = session.sql(variant["sql"]).rows()
    expected = [tuple(r) for r in
                variant["oracle"].expected(frames).itertuples(index=False)]
    assert expected and any(expected[0]), "the filter selects no row"
    assert len(got) == len(expected)
    for g, e in zip(got, expected):
        # text as text, every number an integer and equal
        assert tuple(g) == tuple(int(x) if not isinstance(x, str) else x
                                 for x in e)
        assert all(isinstance(x, (int, str)) for x in g)


# --- the parser and the analyzer --------------------------------------------

def test_function_names_are_names_where_no_call_follows(share):
    _, s = share
    by_alias = s.sql(
        "select year(lo_orderdate) as year, month(lo_orderdate) as month, "
        "count(*) as day from lineorder_flat "
        "group by year, month order by year desc, month limit 3").rows()
    by_call = s.sql(
        "select year(lo_orderdate), month(lo_orderdate), count(*) "
        "from lineorder_flat group by year(lo_orderdate), "
        "month(lo_orderdate) order by year(lo_orderdate) desc, "
        "month(lo_orderdate) limit 3").rows()
    assert by_alias == by_call and by_alias[0][:2] == (1998, 1)
    # the call form is still a call, in every clause
    assert s.sql("select count(*) from lineorder_flat "
                 "where year(lo_orderdate) = 1997 and week(lo_orderdate) = 6"
                 ).rows()[0][0] > 0
    # a column may be called as a function is
    s.sql("create table dated (year int, day int, date int)")
    s.sql("insert into dated values (1997, 3, 5), (1998, 4, 6)")
    assert s.sql("select year, day + date from dated where year > 1997 "
                 "order by year").rows() == [(1998, 10)]
    # and a reserved word is still not a name
    with pytest.raises(ParseError):
        parse("select from from dated")
    with pytest.raises(ParseError):
        parse("select year( from dated")


def test_an_alias_in_group_by_and_order_by_is_its_select_item(share):
    _, s = share
    rows = s.sql("select sum(lo_revenue) as revenue, p_mfgr as maker "
                 "from lineorder_flat group by maker "
                 "order by revenue desc").rows()
    assert len(rows) == 5 and rows == sorted(rows, reverse=True)
    # an alias of an aggregate cannot group
    with pytest.raises(AnalyzerError):
        s.sql("select sum(lo_revenue) as r from lineorder_flat group by r")


def test_names_resolve_without_regard_to_case(share):
    _, s = share
    lower = s.sql("select max(lo_quantity), min(c_city) from lineorder_flat "
                  "where s_region = 'ASIA'").rows()
    assert lower == s.sql(
        "SELECT MAX(LO_QUANTITY), MIN(C_CITY) FROM LINEORDER_FLAT "
        "WHERE S_REGION = 'ASIA'").rows()
    assert lower == s.sql(
        "select max(F.Lo_Quantity), min(f.c_City) from lineorder_flat f "
        "where f.S_region = 'ASIA'").rows()
    assert lower == s.sql(
        "select max(lo_quantity), min(Lineorder_Flat.c_city) "
        "from lineorder_flat where LINEORDER_FLAT.s_region = 'ASIA'").rows()
    # text is still compared as written
    assert s.sql("select count(*) from lineorder_flat "
                 "where s_region = 'asia'").rows() == [(0,)]
    with pytest.raises(AnalyzerError, match="unknown column"):
        s.sql("select lo_orderdat from lineorder_flat")
    # the spelling as written wins where two columns differ by case only,
    # and a spelling that matches neither is ambiguous
    s.sql("create table cased (Ab int, aB int)")
    s.sql("insert into cased values (1, 2)")
    assert s.sql("select aB, Ab from cased").rows() == [(2, 1)]
    with pytest.raises(AnalyzerError, match="ambiguous"):
        s.sql("select ab from cased")
    with pytest.raises(AnalyzerError, match="ambiguous"):
        s.sql("select lo_orderkey from lineorder_flat a, lineorder_flat b")


# --- counters and scopes the cell's metrics read ------------------------------

def _compact_counters() -> dict:
    return {name[len("sr_tpu_compact"):]: value for name, (_, value)
            in metrics.snapshot_values().items()
            if name.startswith("sr_tpu_compact")}


def test_compaction_counters_follow_the_program_that_ran(share):
    _, s = share
    sql = next(v["sql"] for v in CELL.variants
               if v["template"] == "ssb_flat.q3.1")
    s.sql(sql), s.sql(sql)  # learn the capacity, then compile at it
    for _ in range(2):  # the second of these runs a cached program
        before = _compact_counters()
        result = s.sql(sql)
        moved = {k: v - before[k] for k, v in _compact_counters().items()}
        attempts = result.profile.children
        assert len(attempts) == 1 and "compiles" not in attempts[0].counters
        (done,) = attempts[0].infos["compactions"].values()
        assert moved == {"ions_total": 1, "_rows_in_total": done["cap"],
                         "_slots_out_total": done["out_cap"],
                         "_rows_live_total": done["live"]}
        # the filter keeps 1 row in 28: the aggregate runs at the live
        # rows' capacity, not the table's
        assert done["cap"] >= 299_000 and done["live"] <= done["out_cap"]
        assert done["out_cap"] < done["cap"] // 8
    # a statement that compacts nothing moves nothing
    before = _compact_counters()
    s.sql(CELL.variants[0]["sql"])
    assert _compact_counters() == before


@pytest.mark.parametrize("template,scope", [
    ("ssb_flat.q1.3", "sr.filter.2/datepart/weekofyear"),
    ("ssb_flat.q2.1", "sr.agg.2/datepart/year"),
    ("ssb_flat.q4.3", "sr.agg.2/datepart/year"),
])
def test_date_parts_carry_a_scope_under_their_plan_node(share, template, scope):
    _, s = share
    sql = next(v["sql"] for v in CELL.variants if v["template"] == template)
    text = lowered_text(s, s.sql(sql))
    stacks = {path.rsplit("/", 1)[0] for path in SCOPED.findall(text)}
    assert any(stack.endswith(scope) for stack in stacks), sorted(stacks)
    # the arithmetic is there and nowhere else: no division outside it
    divides = [p for p in SCOPED.findall(text) if "floor_divide" in p]
    assert divides and all("/datepart/" in p for p in divides)
    # and the text without debug info does not know about it
    assert "datepart" not in lowered_text(s, s.sql(sql), debug_info=False)


@pytest.mark.parametrize("template", ["ssb_flat.q3.3", "ssb_flat.q3.4"])
def test_a_city_in_list_is_compares_on_the_codes(share, template):
    """`c_city in ('UNITED KI1', 'UNITED KI5') and s_city in (...)`: two runs
    of one code in each 250-entry dictionary, so four equalities beside the
    date compares and no table gathered a row (1.4 s a statement over 75.0M
    rows on a v5e, PERF.md section 6, PR 33)."""
    _, s = share
    sql = next(v["sql"] for v in CELL.variants if v["template"] == template)
    result = s.sql(sql)
    preds = result.profile.children[-1].infos["dict_predicates"]
    assert list(preds) == ["sr.filter.3"]
    assert preds["sr.filter.3"] == [
        {"column": f"lineorder_flat.{c}_CITY", "dict": 250, "true_codes": 2,
         "runs": 2, "formulation": "ranges"} for c in "CS"]
    under_filter = {p.rsplit("/", 1)[1]
                    for p in SCOPED.findall(lowered_text(s, result))
                    if "/sr.filter.3/" in p}
    assert under_filter == {"eq", "or", "ge", "le", "and"}


def test_date_parts_in_int32_equal_the_calendar_over_every_date():
    """year() / month() / day() / weekofyear() of a column run their
    civil-from-days arithmetic in int32 (in int64 a TPU emulates each
    division): exact from 0001-01-01 to 9999-12-31."""
    import datetime

    import jax.numpy as jnp
    import numpy as np

    from starrocks_tpu.exprs.compile import _civil_from_days, _days_from_civil

    epoch = datetime.date(1970, 1, 1).toordinal()
    first, last = 1 - epoch, datetime.date(9999, 12, 31).toordinal() - epoch
    rng = np.random.default_rng(32)
    days = np.unique(np.concatenate([
        [first, first + 1, -1, 0, 1, last - 1, last],
        rng.integers(first, last + 1, 20_000),
        np.arange(8035, 10_441)]))  # every SSB order date
    y, m, d = (np.asarray(x) for x in _civil_from_days(
        jnp.asarray(days, jnp.int32), jnp.int32))
    assert y.dtype == m.dtype == d.dtype == np.int32
    want = [datetime.date.fromordinal(int(x) + epoch) for x in days]
    assert [(a, b, c) for a, b, c in zip(y, m, d)] == [
        (w.year, w.month, w.day) for w in want]
    back = np.asarray(_days_from_civil(jnp.asarray(y), jnp.asarray(m),
                                       jnp.asarray(d), jnp.int32))
    assert back.dtype == np.int32 and np.array_equal(back, days)
    # the default keeps int64, and agrees
    y64, _, _ = _civil_from_days(jnp.asarray(days, jnp.int32))
    assert y64.dtype == jnp.int32 and np.array_equal(np.asarray(y64), y)


def test_weekofyear_is_the_iso_week(share):
    import datetime

    _, s = share
    s.sql("create table days (d date)")
    start = datetime.date(1991, 12, 20)
    dates = [start + datetime.timedelta(days=i) for i in range(2600)]
    s.sql("insert into days values " + ",".join(
        f"('{x.isoformat()}')" for x in dates))
    rows = s.sql("select d, weekofyear(d), year(d) from days order by d").rows()
    assert [(r[1], r[2]) for r in rows] == [
        (x.isocalendar()[1], x.year) for x in dates]


def test_sort_path_packs_its_key_in_int32_where_domain_and_chunk_admit():
    """A GROUP BY whose packed domain (year x brand: 7,000) is over the
    groups' capacity sorts one packed key: an int32 where the domain fits
    one and the chunk has 65,536 rows or more (a TPU sorts an int64 as two
    u32 operands; at 901,120 rows XLA compiled that argsort in 81 s against
    35 s), the int64 of before otherwise, with the same codes either way."""
    import jax.numpy as jnp
    import numpy as np

    from starrocks_tpu import types as T
    from starrocks_tpu.exprs.compile import EVal
    from starrocks_tpu.ops.aggregate import (NARROW_SORT_KEY_ROWS,
                                             _packed_sort_codes)

    rng = np.random.default_rng(7)

    def keys(n, hi):
        return (EVal(jnp.asarray(rng.integers(1992, 1999, n), jnp.int32),
                     None, T.INT, bounds=(1992, 1998)),
                EVal(jnp.asarray(rng.integers(0, hi + 1, n), jnp.int32),
                     None, T.INT, bounds=(0, hi)))

    n = NARROW_SORT_KEY_ROWS
    live = jnp.asarray(rng.random(n) < 0.5)
    big = keys(n, 999)
    narrow = _packed_sort_codes(big, live)
    assert narrow.dtype == jnp.int32
    year, brand = (np.asarray(k.data) for k in big)
    want = np.where(np.asarray(live), (year - 1992) * 1000 + brand, 7000)
    assert np.array_equal(np.asarray(narrow), want)
    # a domain over 2^31 - 1, or a small chunk: the int64 of before
    assert _packed_sort_codes(keys(n, (1 << 29) - 1), live).dtype == jnp.int64
    small = _packed_sort_codes(keys(n // 2, 999), live[:n // 2])
    assert small.dtype == jnp.int64


def test_a_group_by_over_its_capacity_sorts_an_int32_key_and_answers_right(
        share, frames):
    import re

    _, s = share
    result = s.sql(
        "select c_city, s_city, year(lo_orderdate) as year, "
        "sum(lo_revenue) as revenue, count(*) as n from lineorder_flat "
        "group by c_city, s_city, year order by year, c_city, s_city")
    t = frames["lineorder_flat"]
    want = (t.assign(year=t.LO_ORDERDATE.dt.year,
                     revenue=t.LO_REVENUE.astype("int64"))
            .groupby(["C_CITY", "S_CITY", "year"], as_index=False,
                     observed=True)
            .agg(revenue=("revenue", "sum"), n=("revenue", "size"))
            .sort_values(["year", "C_CITY", "S_CITY"]))
    assert result.rows() == [
        (str(r.C_CITY), str(r.S_CITY), int(r.year), int(r.revenue), int(r.n))
        for r in want.itertuples(index=False)]
    # 250 x 250 x 7 = 437,500 packed codes for ~200,000 groups of capacity:
    # the sort path, its one key an int32
    sums = result.profile.children[-1].infos["segment_sums"]["sr.agg.2"]
    assert sums["formulation"] == "sorted" and sums["rows"] >= 1 << 16
    text = lowered_text(s, result, debug_info=False)
    sorts = re.findall(r'"stablehlo\.sort"\(.*?\) -> \((.*?)\)', text, re.S)
    # (the row index beside the key is jnp.argsort's int64, as before)
    assert sorts[0].split(", ")[0] == f"tensor<{sums['rows']}xi32>", sorts
