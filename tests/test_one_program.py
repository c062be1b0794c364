"""One program on every backend: the engine traces the same formulations
whatever `jax.default_backend()` says, so what tier-1 checks here on CPU
devices is what the chip runs. Covered: the window top-N prefilter's
`top_k` threshold, the residual SEMI/ANTI join's sort + `searchsorted`
membership (both shipped to the chip with no test before), the source
itself, and the options that went with the second engine."""

import os
import re

import numpy as np
import pandas as pd
import pytest

from starrocks_tpu.column import HostTable
from starrocks_tpu.runtime.session import Session
from starrocks_tpu.storage.catalog import Catalog

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "starrocks_tpu")

K = 4


def _window_frame(partitions: int) -> pd.DataFrame:
    """Few distinct order keys, so every partition has ties at its K-th
    key, and a partition with fewer than K rows when there are many."""
    rng = np.random.default_rng(17 + partitions)
    n = 3000
    f = pd.DataFrame({
        "p": rng.integers(0, partitions, n).astype(np.int64),
        "v": rng.integers(0, 40, n).astype(np.int64)})
    if partitions > 1:
        f = pd.concat([f[f.p != 0], f[f.p == 0].head(K - 2)],
                      ignore_index=True)
    return f


@pytest.mark.parametrize("partitions", [1, 19], ids=["one", "many"])
@pytest.mark.parametrize("fn", ["row_number", "rank", "dense_rank"])
def test_window_topn_threshold_matches_the_full_window_rank(fn, partitions):
    """`<fn>() <= K` through the per-partition `[D, cap]` `top_k`
    threshold against pandas' rank over the whole window, ties at the
    K-th key included. `rank` and `row_number` count rows, so the
    threshold drops exactly the rows under each partition's K-th key
    before the sort; `dense_rank` counts distinct keys and must not be
    prefiltered (its K-th rank lies past the K-th row)."""
    f = _window_frame(partitions)
    cat = Catalog()
    cat.register("t", HostTable.from_pydict(f.to_dict("list")))
    by = "partition by p " if partitions > 1 else ""
    s = Session(cat)
    got = s.sql(
        f"select p, v, r from (select p, v, {fn}() over ({by}order by v "
        f"desc) r from t) x where r <= {K} order by p, v desc, r").rows()

    group = f.groupby("p") if partitions > 1 else f.assign(p=0).groupby("p")
    method = {"row_number": "first", "rank": "min", "dense_rank": "dense"}[fn]
    f["r"] = group.v.rank(method=method, ascending=False).astype(np.int64)
    want = f[f.r <= K].sort_values(
        ["p", "v", "r"], ascending=[True, False, True])
    assert got == [tuple(int(x) for x in row) for row in
                   want[["p", "v", "r"]].itertuples(index=False)]
    # ties at the K-th key really occur: rank keeps more than K rows
    if fn == "rank":
        assert (want.groupby("p").size() > K).any()

    dropped = s.last_profile.counters.get("window_topn_prefiltered", (0,))[0]
    if fn == "dense_rank":
        assert dropped == 0
    else:
        # exact: every row under its partition's K-th largest key, no other
        kth = group.v.transform(
            lambda v: np.sort(v.to_numpy())[::-1][min(K, len(v)) - 1])
        assert dropped == int((f.v < kth).sum()) > 0


def _semi_frames():
    """Probe and build with duplicate keys on both sides (many matches a
    probe row) and NULL keys on both sides (never equal to anything)."""
    rng = np.random.default_rng(29)
    n, m = 1500, 4000

    def side(rows):
        k = rng.integers(0, 300, rows).astype(object)
        k[rng.random(rows) < 0.08] = None
        return pd.DataFrame({
            "k": k, "s": rng.integers(0, 6, rows).astype(np.int64),
            "id": np.arange(rows, dtype=np.int64)})

    return side(n), side(m)


@pytest.fixture(scope="module")
def semi_session():
    l1, l2 = _semi_frames()
    cat = Catalog()
    cat.register("l1", HostTable.from_pydict(l1.to_dict("list")))
    cat.register("l2", HostTable.from_pydict(l2.to_dict("list")))
    return Session(cat), l1, l2


@pytest.mark.parametrize("residual", ["<>", "<"], ids=["ne", "lt"])
@pytest.mark.parametrize("kind", ["semi", "anti"])
def test_residual_semi_anti_join_matches_pandas(semi_session, kind, residual):
    """TPC-H Q21's shape: EXISTS / NOT EXISTS with an equality and a
    correlated inequality. The join expands on the key, filters by the
    residual and reduces the surviving (duplicate) probe row ids to a
    membership mask by sort + `searchsorted`."""
    s, l1, l2 = semi_session
    neg = "not " if kind == "anti" else ""
    got = s.sql(
        f"select id from l1 where {neg}exists (select * from l2 "
        f"where l2.k = l1.k and l2.s {residual} l1.s) order by id").rows()

    pairs = l1.dropna(subset=["k"]).merge(
        l2.dropna(subset=["k"]), on="k", suffixes=("", "_b"))
    ok = pairs.s_b != pairs.s if residual == "<>" else pairs.s_b < pairs.s
    matches = pairs[ok].groupby("id").size()
    assert matches.max() > 10  # many matches a probe row
    member = l1.id.isin(matches.index)
    want = l1.id[~member if kind == "anti" else member]
    assert [r[0] for r in got] == sorted(int(x) for x in want)
    # a NULL probe key matches nothing: NOT EXISTS keeps it, EXISTS drops it
    nulls = set(l1.id[l1.k.isna()])
    assert nulls and (nulls <= {r[0] for r in got}) == (kind == "anti")


def test_residual_semi_join_lowers_without_a_scatter(semi_session):
    """The membership test is a sort and a binary search directly under
    the join's scope: no scatter on duplicate row ids (the shape a TPU
    serializes on; the runtime filter's and the expansion's scatters have
    phases of their own)."""
    from lowering import SCOPED, lowered_text
    from starrocks_tpu.ops.common import PHASES

    s, _, _ = semi_session
    r = s.sql("select count(*) c from l1 where exists (select * from l2 "
              "where l2.k = l1.k and l2.s <> l1.s)")
    own = set()  # operations of the join node itself, outside every phase
    for path in SCOPED.findall(lowered_text(s, r)):
        below = re.split(r"sr\.join\.\d+/", path)[-1]
        if "sr.join." in path and "sr." not in below \
                and below.split("/")[0] not in PHASES:
            own.add(below.split("/")[0])
    assert {"jit(sort)", "jit(searchsorted)"} <= own, sorted(own)
    assert not [op for op in own if op.startswith("scatter")], sorted(own)


BACKEND_READS = re.compile(
    r"default_backend|\.platform\b|\.device_kind\b|on_tpu|_use_mxu|pallas")


def test_no_module_reads_the_backend():
    """No module of the package asks which backend it runs on, and none
    names the kernels or the rule that went: `chip_smoke.py`'s and
    `benchmarks/run.py`'s refusal to run off a TPU lie outside it."""
    found = []
    for root, _, files in os.walk(PACKAGE):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            with open(path) as fh:
                for n, line in enumerate(fh, 1):
                    if BACKEND_READS.search(line):
                        found.append(f"{os.path.relpath(path, PACKAGE)}:{n}: "
                                     f"{line.strip()}")
    assert not found, "\n".join(found)


@pytest.mark.parametrize("name,value", [
    ("segment_strategy", "'scatter'"), ("join_probe_strategy", "'pallas'"),
    ("enable_scatter_free_segments", "false"), ("dense_agg_domain_max", "64"),
    ("bench_sf", "1"),
])
def test_set_of_a_deleted_option_is_an_unknown_variable(name, value):
    s = Session()
    with pytest.raises(KeyError) as unknown:
        s.sql("SET no_such_option = 1")
    with pytest.raises(KeyError) as gone:
        s.sql(f"SET {name} = {value}")
    assert str(gone.value) == str(unknown.value).replace(
        "no_such_option", name)
