"""A boolean predicate over a dictionary column is evaluated on its codes
(`exprs/compile.dict_code_mask`): a set of TRUE codes, split into its runs of
consecutive codes, is compares on the codes (`ranges`) up to
`RANGES_MAX_RUNS` runs and the boolean table gathered a row (`lut`) above.
Both formulations give the mask the table gives on every valid row, for
every SQL predicate built on it, with the validity SQL asks for; which one a
program took is its `dict_predicates` info."""

import re

import jax.numpy as jnp
import numpy as np
import pytest

from starrocks_tpu.exprs import compile as C
from starrocks_tpu.runtime.session import Session
from starrocks_tpu.storage.catalog import tpch_catalog

from lowering import SCOPED, lowered_text
# (the fixture: tables over 1,000 rows shard, as SF10's do over 100,000)
from test_dist_observability import small_tables_shard  # noqa: F401

# either side of the crossover: every set is `ranges`, every set is `lut`
FORMULATIONS = {"ranges": 1 << 30, "lut": -1}


@pytest.fixture(params=list(FORMULATIONS))
def formulation(request, monkeypatch):
    monkeypatch.setattr(C, "RANGES_MAX_RUNS", FORMULATIONS[request.param])
    return request.param


# --- the helper, on sets of every shape ---------------------------------------

D = 40
SETS = {
    "empty": [],
    "whole-dictionary": list(range(D)),
    "one-code": [17],
    "one-run": list(range(9, 23)),
    "first-code": [0],
    "last-code": [D - 1],
    "runs-touching-both-ends": [0, 1, 2, 20, D - 2, D - 1],
    "every-other-code": list(range(0, D, 2)),
    "two-cities": [11, 15],
}


@pytest.mark.parametrize("case", list(SETS))
def test_mask_equals_the_table_on_valid_rows(case, formulation):
    rng = np.random.default_rng(len(case))
    codes = rng.integers(0, D, 5_000).astype(np.int32)
    null = rng.random(codes.size) < 0.05  # a NULL row carries any code
    codes[null] = rng.choice([-1, D, 2**31 - 1], int(null.sum()))
    true = np.zeros(D, np.bool_)
    true[SETS[case]] = True
    sink: dict = {}
    with C.dict_predicate_log(sink, "sr.filter.1"):
        got = np.asarray(C.dict_code_mask(jnp.asarray(codes), true, "t.c"))
    assert got.dtype == np.bool_ and got.shape == codes.shape
    assert np.array_equal(got[~null], true[codes[~null]])
    runs = len(re.findall("1+", "".join("01"[int(b)] for b in true)))
    assert sink == {"sr.filter.1": [{
        "column": "t.c", "dict": D, "true_codes": len(SETS[case]),
        "runs": runs, "formulation": formulation}]}


def test_an_empty_dictionary_matches_nothing():
    got = C.dict_code_mask(jnp.zeros((8,), jnp.int32), np.zeros(0, np.bool_))
    assert not np.asarray(got).any()


def test_the_crossover_is_the_run_count_alone():
    """RANGES_MAX_RUNS runs are compares, one more is the table, whatever the
    dictionary's length; nothing is recorded where no plan node is open."""
    codes = jnp.arange(4 * C.RANGES_MAX_RUNS + 8, dtype=jnp.int32)
    for runs, want in ((C.RANGES_MAX_RUNS, "ranges"),
                       (C.RANGES_MAX_RUNS + 1, "lut")):
        true = np.zeros(codes.size, np.bool_)
        true[np.arange(runs) * 3] = True
        sink: dict = {}
        with C.dict_predicate_log(sink, "s"):
            got = C.dict_code_mask(codes, true)
        assert np.array_equal(np.asarray(got), true)
        assert [p["formulation"] for p in sink["s"]] == [want]
        assert sink["s"][0]["runs"] == runs
        C.dict_code_mask(codes, true)  # outside any log: no error, no record
        assert len(sink["s"]) == 1


# --- every SQL predicate built on it, with NULL rows --------------------------

WORDS = ["apple", "apricot", "banana", "blueberry", "cherry", "fig", "grape",
         "green apple", "lemon", "lime", "mango", "peach", "pear", "plum"]


def _like(pattern):
    rx = re.compile(C.like_to_regex(pattern), re.S)
    return lambda s: rx.match(s) is not None


def _in(values, negated=False):
    """SQL's three-valued IN over a non-NULL `s`."""
    def f(s):
        if s in [v for v in values if v is not None]:
            return not negated
        return None if None in values else negated
    return f


# name -> (SQL over column c, the same over one non-NULL Python string)
PREDICATES = {
    "in": ("c in ('fig', 'lime', 'pear')", _in(["fig", "lime", "pear"])),
    "in-absent-value": ("c in ('fig', 'durian')", _in(["fig", "durian"])),
    "in-nothing-present": ("c in ('durian', 'kiwi')",
                           _in(["durian", "kiwi"])),
    "in-first-and-last": ("c in ('apple', 'plum')", _in(["apple", "plum"])),
    "in-whole-dictionary": (
        "c in (" + ", ".join(f"'{w}'" for w in WORDS) + ")", _in(WORDS)),
    "not-in": ("c not in ('fig', 'lime', 'pear')",
               _in(["fig", "lime", "pear"], negated=True)),
    "in-with-null": ("c in ('fig', 'lime', null)",
                     _in(["fig", "lime", None])),
    "not-in-with-null": ("c not in ('fig', 'lime', null)",
                         _in(["fig", "lime", None], negated=True)),
    "like-prefix": ("c like 'ap%'", _like("ap%")),
    "like-infix": ("c like '%e%'", _like("%e%")),
    "like-nothing": ("c like 'zz%'", _like("zz%")),
    "not-like": ("c not like '%an%'", lambda s: not _like("%an%")(s)),
    "starts-with": ("starts_with(c, 'p')", lambda s: s.startswith("p")),
    "ends-with": ("ends_with(c, 'e')", lambda s: s.endswith("e")),
}


def _session():
    rng = np.random.default_rng(33)
    rows = [None if rng.random() < 0.1 else WORDS[rng.integers(len(WORDS))]
            for _ in range(600)]
    s = Session()
    s.sql("create table t (id bigint, c varchar)")
    s.sql("insert into t values " + ", ".join(
        f"({i}, {'null' if w is None else repr(w)})"
        for i, w in enumerate(rows)))
    return s, rows


@pytest.mark.parametrize("name", list(PREDICATES))
def test_sql_predicate_equals_python_mask_and_validity(name, formulation):
    sql, reference = PREDICATES[name]
    s, rows = _session()
    want = [None if w is None else reference(w) for w in rows]
    result = s.sql(f"select id, {sql} as p from t order by id")
    got = [None if p is None else bool(p) for _, p in result.rows()]
    assert got == want
    # the WHERE form: NULL is not TRUE
    kept = s.sql(f"select id from t where {sql} order by id").rows()
    assert [i for i, in kept] == [i for i, p in enumerate(want) if p is True]
    (preds,) = result.profile.children[-1].infos["dict_predicates"].values()
    assert [p["formulation"] for p in preds] == [formulation]
    # (an INSERT keeps '' in the dictionary for its NULL rows' codes)
    assert preds[0]["column"] == "t.c" and preds[0]["dict"] == len(WORDS) + 1


# --- what the program holds ---------------------------------------------------

def _filter_gathers(text: str) -> list:
    """Name stacks of the gathers whose innermost plan node is a filter."""
    out = []
    for path in SCOPED.findall(text):
        nodes = [c for c in path.split("/") if c.startswith("sr.")]
        if path.endswith("/gather") and nodes[-1].startswith("sr.filter."):
            out.append(path)
    return out


def test_a_scattered_like_keeps_the_table_and_an_in_list_does_not():
    """900 distinct strings, every third holds an `x`: `LIKE '%x%'` is 300
    runs and stays a gather under its filter; an IN list of two is two
    compares and the filter holds no gather."""
    words = [f"w{i:03d}{'x' if i % 3 == 0 else 'y'}" for i in range(900)]
    s = Session()
    s.sql("create table w (id bigint, c varchar)")
    s.sql("insert into w values " + ", ".join(
        f"({i}, '{w}')" for i, w in enumerate(words)))
    like = s.sql("select count(*) from w where c like '%x%'")
    assert like.rows() == [(300,)]
    (preds,) = like.profile.children[-1].infos["dict_predicates"].values()
    assert preds == [{"column": "w.c", "dict": 900, "true_codes": 300,
                      "runs": 300, "formulation": "lut"}]
    assert _filter_gathers(lowered_text(s, like))
    two = s.sql("select count(*) from w where c in ('w003x', 'w200y')")
    assert two.rows() == [(2,)]
    (preds,) = two.profile.children[-1].infos["dict_predicates"].values()
    assert preds == [{"column": "w.c", "dict": 900, "true_codes": 2,
                      "runs": 2, "formulation": "ranges"}]
    text = lowered_text(s, two)
    assert not _filter_gathers(text) and "gather" not in text
    # a cached program reports what its trace found
    again = s.sql("select count(*) from w where c in ('w003x', 'w200y')")
    attempt = again.profile.children[-1]
    assert "compiles" not in attempt.counters
    assert attempt.infos["dict_predicates"] == two.profile.children[
        -1].infos["dict_predicates"]


def test_a_mesh_statement_reports_its_fragments_predicates(
        small_tables_shard):
    """Fragment programs evaluate a predicate through the same compiler: the
    statement's `dict_predicates` holds what they recorded, from a cached
    program too, and the answer is the one-chip one."""
    cat = tpch_catalog(0.01)
    sql = ("select l_shipmode, count(*) from lineitem "
           "where l_shipmode in ('MAIL', 'SHIP') "
           "and l_shipinstruct like 'DELIVER%' "
           "group by l_shipmode order by l_shipmode")
    dist = Session(cat, dist_shards=4)
    sends = [dist.sql(sql) for _ in range(3)]
    assert sends[-1].rows() == Session(cat).sql(sql).rows()
    attempt = sends[-1].profile.children[-1]
    assert "compiles" not in attempt.counters and len(
        attempt.infos["programs"]) == 2
    assert attempt.infos["dict_predicates"] == {"sr.filter.3": [
        {"column": "lineitem.l_shipmode", "dict": 7, "true_codes": 2,
         "runs": 2, "formulation": "ranges"},
        {"column": "lineitem.l_shipinstruct", "dict": 4, "true_codes": 1,
         "runs": 1, "formulation": "ranges"}]}
