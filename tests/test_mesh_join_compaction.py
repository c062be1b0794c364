"""The mesh compiler shrinks a join's sparse sides to their live rows before
anything priced per slot runs (the shuffle's pack, the search, the payload
gathers, the GROUP BY at the join's capacity), by the rule the one-chip
compiler applies (`sql/physical.shrink_capacity`, `join_side_estimates`),
a shard: on four virtual CPU devices, right against pandas, under the same
capacity keys whether the plan compiles whole or a fragment at a time, and
recovering from an underestimate. The rule was lifted out of
`compile_plan`'s `maybe_compact`: the one-chip programs, and the mesh
programs of a statement with no join, lower to the text they lowered to
before (`tests/data/lowered_before_mesh_compaction.json`; to write it anew
from a tree: `python tests/test_mesh_join_compaction.py > <that file>`)."""

import hashlib
import json
import os
import re
import sys

import numpy as np
import pandas as pd
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if __name__ == "__main__":  # run from any tree, on four virtual devices
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    sys.path[:0] = [os.path.dirname(HERE), HERE]

import starrocks_tpu.sql.distributed as D
from starrocks_tpu.column import HostTable
from starrocks_tpu.runtime.config import config
from starrocks_tpu.runtime.session import Session
from starrocks_tpu.storage.catalog import Catalog, tpch_catalog

from lowering import lowered_text
# (the fixture: tables over 1,000 rows shard, as SF10's do over 100,000)
from test_dist_observability import _fragments, small_tables_shard  # noqa: F401
from test_tpch_sql import _cmp_rows
from tpch_oracle import ORACLES, load_frames
from tpch_queries import QUERIES

N = 4
LOWERED = os.path.join(HERE, "data", "lowered_before_mesh_compaction.json")
# one-chip statements whose programs the lifted rule decides: Q3 by
# `maybe_compact` under `emit_multiway`, Q7 and Q12 by a binary join's
# `shrink_<n>l` / `shrink_<n>r`; Q1 and Q6 hold no compaction
ONE_CHIP = (1, 3, 6, 7, 12)


def _last_attempt(result) -> dict:
    return result.profile.children[-1].infos


# --- (a) Q3: both joins' probe sides run at their live rows ---------------------

@pytest.fixture(scope="module")
def tpch():
    """SF0.05: `orders` is 18,750 rows a shard, over the rule's 8,192 slots
    (at SF0.01 only lineitem is)."""
    return tpch_catalog(0.05)


@pytest.fixture(scope="module")
def q3_sends(tpch, small_tables_shard):
    dist = Session(tpch, dist_shards=N)
    return [dist.sql(QUERIES[3]) for _ in range(3)]


def test_q3_equals_the_oracle_and_one_chip(q3_sends, tpch):
    got = q3_sends[-1].rows()
    exp = [tuple(r) for r in ORACLES[3](load_frames(tpch))
           .itertuples(index=False)]
    _cmp_rows(got, exp, 3, ordered=False)
    _cmp_rows(got, Session(tpch).sql(QUERIES[3]).rows(), 3, ordered=False)
    assert all(r.rows() == got for r in q3_sends)
    # warm from the third send, as before: the second compiles once more
    # at the tightened capacities
    assert "compiles" not in q3_sends[-1].profile.children[-1].counters


def test_q3_programs_list_the_probe_shrink_of_both_joins(q3_sends):
    for result in q3_sends:
        attempt = _last_attempt(result)
        held = {re.search(r"_f(\d+)\Z", name).group(1): holds["compactions"]
                for name, holds in attempt["programs"].items()}
        # `orders` before it is shuffled to `customer`'s placement (f1);
        # lineitem before it is searched against the shuffled build (f2)
        assert "shrink_6l" in held["1"] and "shrink_3l" in held["2"]
        for fid, key in (("1", "shrink_6l"), ("2", "shrink_3l")):
            c = held[fid][key]
            assert 0 < c["live"] <= c["out_cap"] < c["cap"], (key, c)
            assert c["method"] == "shift"
            assert attempt["compactions"][key] == c
            assert attempt["capacities"][key] == c["out_cap"]
        # the first shuffle's buckets follow the compacted capacity, not
        # the 19,456 slots `orders` came in
        assert attempt["capacities"]["shufL_6"] <= D._default_bucket_cap(
            held["1"]["shrink_6l"]["out_cap"], N) < D._default_bucket_cap(
            held["1"]["shrink_6l"]["cap"], N)
    # the learning loop tightened lineitem's over-seeded capacity (the
    # planner expects ~4x the rows that survive) for the second send
    first, warm = (_last_attempt(r)["compactions"]["shrink_3l"]
                   for r in (q3_sends[0], q3_sends[-1]))
    assert warm["out_cap"] < first["out_cap"] and warm["live"] == first["live"]


# --- (b) every join kind, whole plan and fragment path alike --------------------

ROWS, KEYS = 48_000, 4_000  # 12,000 probe rows a shard


@pytest.fixture(scope="module")
def star():
    """A probe table `f` of 48,000 rows over keys 0..4,999 (a fifth match
    nothing) and two build tables: `d` unique on its key, `e` with three
    rows a key. `a` and `b` are the same column twice: the planner
    multiplies their selectivities, the data does not."""
    rng = np.random.default_rng(30)
    a = rng.integers(0, 100, ROWS)
    f = pd.DataFrame({
        "id": np.arange(ROWS), "k": rng.integers(0, KEYS + 1000, ROWS),
        "v": rng.integers(0, 100, ROWS), "a": a, "b": a.copy()})
    d = pd.DataFrame({"k": np.arange(KEYS), "w": np.arange(KEYS) * 7})
    e = pd.DataFrame({"k": np.repeat(np.arange(KEYS), 3),
                      "w": np.arange(KEYS * 3)})
    cat = Catalog()
    cat.register("f", HostTable.from_pydict(f.to_dict("list")))
    cat.register("d", HostTable.from_pydict(d.to_dict("list")),
                 unique_keys=(("k",),))
    cat.register("e", HostTable.from_pydict(e.to_dict("list")))
    return cat, f, d, e


def _want(kind: str, f, build, keep) -> list:
    """pandas' answer, as sorted (id, w or None) rows."""
    probe = f[keep]
    if kind in ("semi", "anti"):
        member = probe.k.isin(build.k)
        ids = probe.id[member if kind == "semi" else ~member]
        return sorted((int(i), None) for i in ids)
    m = probe.merge(build, on="k", how=kind)
    return sorted((int(i), None if pd.isna(w) else int(w))
                  for i, w in zip(m.id, m.w))


CASES = {
    "inner": ("d", "select f.id, d.w from f join d on f.k = d.k "
                   "where f.v < 5"),
    "left": ("d", "select f.id, d.w from f left join d on f.k = d.k "
                  "where f.v < 5"),
    "semi": ("d", "select f.id, null from f where f.v < 5 and f.k in "
                  "(select k from d)"),
    "anti": ("d", "select f.id, null from f where f.v < 5 and f.k not in "
                  "(select k from d)"),
    "inner-expanding": ("e", "select f.id, e.w from f join e on f.k = e.k "
                             "where f.v < 5"),
}


@pytest.mark.parametrize("case", list(CASES))
def test_join_kinds_equal_pandas_under_the_same_keys_whole_and_in_fragments(
        star, small_tables_shard, case):
    cat, f, d, e = star
    table, sql = CASES[case]
    want = _want(case.split("-")[0], f, {"d": d, "e": e}[table], f.v < 5)
    assert len(want) > 400
    keys = {}
    for fragments in (True, False):
        config.set("dist_fragments", fragments)
        try:
            dist = Session(cat, dist_shards=N)
            sends = [dist.sql(sql) for _ in range(2)]
        finally:
            config.set("dist_fragments", True)
        for result in sends:
            assert sorted(result.rows()) == want
        attempt = _last_attempt(sends[-1])
        keys[fragments] = set(attempt["capacities"])
        # 5% of the probe side's 12,000 slots a shard are live: it is
        # searched (and, where the build is placed elsewhere, shuffled)
        # at its live rows. LEFT / SEMI / ANTI run no runtime filter
        # that could drop more than the WHERE did
        (key, c), = [(k, c) for k, c in attempt["compactions"].items()
                     if re.fullmatch(r"shrink_\d+l", k)]
        assert ROWS // N * 0.03 < c["live"] <= c["out_cap"] < c["cap"]
        assert c["cap"] >= ROWS // N
        assert len(attempt["programs"]) == (
            sends[-1].profile.infos["fragments"] if fragments else 1)
    assert keys[True] == keys[False] and keys[True] >= {key}


# --- (c) an underestimate recovers ---------------------------------------------

def test_probe_shrink_seeded_below_the_live_rows_reruns_and_answers_right(
        star, small_tables_shard):
    """`a < 30 and b < 30 and a + b < 60` keeps three tenths of `f` (a =
    b), the planner multiplies three selectivities: the shrink is seeded
    under a shard's live rows, its check overflows, the statement reruns
    at `live x join_expand_headroom` and answers right."""
    cat, f, d, _ = star
    dist = Session(cat, dist_shards=N)
    sql = ("select f.id, d.w from f left join d on f.k = d.k "
           "where f.a < 30 and f.b < 30 and f.a + f.b < 60")
    result = dist.sql(sql)
    assert sorted(result.rows()) == _want("left", f, d, f.a < 30)
    assert result.profile.counters["recompiles"][0] >= 1
    first, last = (a.infos["compactions"] for a in result.profile.children
                   if a.name in ("attempt_0",
                                 result.profile.children[-1].name))
    (key, seeded), = [(k, c) for k, c in first.items()
                      if re.fullmatch(r"shrink_\d+l", k)]
    assert seeded["live"] > seeded["out_cap"]  # rows were dropped there
    assert seeded["live"] == last[key]["live"] <= last[key]["out_cap"]
    # learned: the next send compiles nothing over again and is right
    again = dist.sql(sql)
    assert again.profile.counters["recompiles"][0] == 0
    assert sorted(again.rows()) == sorted(result.rows())


# --- (d) what the rule was lifted out of lowers as it did -----------------------

def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


def _lowered_shas(one_chip: Session, dist: Session) -> dict:
    """sha256 of the StableHLO text (no debug info: no source lines) of
    the one-chip Q1 / Q3 / Q6 / Q7 / Q12 at SF0.01, each at the capacities
    its first send ended on, and of the mesh's two fragment programs of Q1
    at the capacities its third send ran at."""
    out = {}
    for q in ONE_CHIP:
        out[f"one_chip_q{q}"] = _sha(lowered_text(
            one_chip, one_chip.sql(QUERIES[q]), debug_info=False))
    for _ in range(3):
        result = dist.sql(QUERIES[1])
    _, _, _, programs = _fragments(dist, result)
    for fid, (fn, args) in programs.items():
        out[f"mesh_q1_f{fid}"] = _sha(fn.lower(*args).as_text())
    return out


@pytest.fixture(scope="module")
def lowered_now(small_tables_shard):
    cat = tpch_catalog(0.01)
    return _lowered_shas(Session(cat), Session(cat, dist_shards=N))


with open(LOWERED) as _f:
    # taken at 03cdf7a, the parent of PR 30; Q7 rewritten by PR 32 (a year in
    # int32), Q12 by PR 33 (`l_shipmode in (...)` as compares on the codes)
    LOWERED_BEFORE = json.load(_f)


@pytest.mark.parametrize("program", sorted(LOWERED_BEFORE))
def test_program_lowers_to_the_text_it_lowered_to_before(lowered_now, program):
    assert lowered_now[program] == LOWERED_BEFORE[program]


if __name__ == "__main__":
    D.SHARD_THRESHOLD_ROWS = 1_000
    _cat = tpch_catalog(0.01)
    print(json.dumps(_lowered_shas(
        Session(_cat), Session(_cat, dist_shards=N)), indent=1))
