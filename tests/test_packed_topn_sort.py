"""Packed-key sort + threshold TopN vs the lexsort reference.

Property tests: random key-type mixes (dict strings, bools, bounded ints),
NULLs, ASC/DESC and NULLS FIRST/LAST combinations — the packed single-key
argsort, the threshold top-N partial select, and the Pallas block-select
kernel must all reproduce the stable lexsort order EXACTLY (ties resolve
to input order on every path). Plus the rank()<=k window rewrite vs a
brute-force oracle, and the new profile counters.
"""

import dataclasses
import re

import numpy as np
import pytest

from starrocks_tpu import types as T
from starrocks_tpu.column import HostTable
from starrocks_tpu.column.column import Chunk, Schema, pad_capacity
from starrocks_tpu.exprs import col
from starrocks_tpu.ops import sort_chunk
from starrocks_tpu.ops.sort import packed_order_key
from starrocks_tpu.ops.common import eval_keys
from starrocks_tpu.runtime.config import config
from starrocks_tpu.runtime.session import Session
from starrocks_tpu.storage.catalog import Catalog


@pytest.fixture(autouse=True)
def _restore_flags():
    saved = {k: config.get(k) for k in
             ("enable_packed_sort_keys", "topn_strategy",
              "enable_window_topn")}
    yield
    for k, v in saved.items():
        config.set(k, v)


def _with_int_bounds(chunk: Chunk, bounds: dict) -> Chunk:
    """Attach catalog-style (lo, hi) bounds to integer fields (the tests
    build chunks directly, bypassing the catalog stats path)."""
    fields = tuple(
        dataclasses.replace(f, bounds=bounds.get(f.name, f.bounds))
        for f in chunk.schema.fields
    )
    return Chunk(Schema(fields), chunk.data, chunk.valid, chunk.sel)


def _gen_columns(rng, n, spec):
    """spec: list of (name, kind) with kind in int|str|bool; ~15% NULLs."""
    data = {}
    ref = {}
    for name, kind in spec:
        nulls = rng.random(n) < 0.15
        if kind == "int":
            v = rng.integers(0, 40, n)
            data[name] = [None if m else int(x) for m, x in zip(nulls, v)]
        elif kind == "bool":
            v = rng.integers(0, 2, n).astype(bool)
            data[name] = [None if m else bool(x) for m, x in zip(nulls, v)]
        else:
            words = ["ash", "birch", "cedar", "dogwood", "elm", "fir"]
            v = rng.integers(0, len(words), n)
            data[name] = [None if m else words[x] for m, x in zip(nulls, v)]
        ref[name] = data[name]
    return data, ref


def _expected_order(ref, sort_keys, n):
    """Stable python sort of row indices under SQL ORDER BY semantics."""
    def keyf(i):
        parts = []
        for name, asc, nulls_first in sort_keys:
            v = ref[name][i]
            null = v is None
            null_rank = (0 if nulls_first else 1) if null else \
                (1 if nulls_first else 0)
            if null:
                num = 0.0
            elif isinstance(v, str):
                num = float(sorted({x for x in ref[name] if x is not None}
                                   ).index(v))
            else:
                num = float(v)
            parts.append((null_rank, num if asc else -num))
        return tuple(parts)

    return sorted(range(n), key=keyf)


def _rows_in_order(chunk, names):
    ht = HostTable.from_chunk(chunk)
    rows = ht.to_pylist()
    idx = [f.name for f in ht.schema]
    pos = [idx.index(nm) for nm in names]
    return [tuple(r[p] for p in pos) for r in rows]


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_packed_sort_matches_lexsort_property(seed):
    rng = np.random.default_rng(seed)
    n = 257 + int(rng.integers(0, 200))
    kinds = ["int", "str", "bool"]
    nk = int(rng.integers(1, 4))
    spec = [(f"k{i}", kinds[int(rng.integers(0, 3))]) for i in range(nk)]
    data, ref = _gen_columns(rng, n, spec)
    chunk = HostTable.from_pydict(data).to_chunk()
    # python bools infer as BIGINT through from_pydict: bound them like
    # the catalog stats would
    chunk = _with_int_bounds(
        chunk, {nm: (0, 39) if kind == "int" else (0, 1)
                for nm, kind in spec if kind in ("int", "bool")})

    sort_keys = tuple(
        (col(nm), bool(rng.integers(0, 2)), bool(rng.integers(0, 2)))
        for nm, _ in spec
    )
    named = [(nm, asc, nf) for (nm, _), (_, asc, nf) in zip(spec, sort_keys)]
    want = _expected_order(ref, named, n)
    names = [nm for nm, _ in spec]
    want_rows = [tuple(ref[nm][i] for nm in names) for i in want]

    # the packed path must actually engage for this all-bounded key mix
    keys = eval_keys(chunk, tuple(e for e, _, _ in sort_keys))
    assert packed_order_key(keys, sort_keys, chunk.sel_mask()) is not None

    config.set("topn_strategy", "auto")
    config.set("enable_packed_sort_keys", True)
    got_packed = _rows_in_order(sort_chunk(chunk, sort_keys), names)
    config.set("enable_packed_sort_keys", False)
    got_lex = _rows_in_order(sort_chunk(chunk, sort_keys), names)

    assert got_packed == want_rows
    assert got_lex == want_rows


@pytest.mark.parametrize("strategy", ["auto"])
def test_threshold_topn_matches_full_sort(strategy):
    rng = np.random.default_rng(7)
    n = 5000
    data = {
        "k": [None if m else int(x) for m, x in
              zip(rng.random(n) < 0.1, rng.integers(0, 1000, n))],
        "payload": list(rng.integers(0, 10**6, n)),
    }
    chunk = HostTable.from_pydict(data).to_chunk()
    chunk = _with_int_bounds(chunk, {"k": (0, 999)})
    sort_keys = ((col("k"), False, False),)  # DESC NULLS LAST

    config.set("enable_packed_sort_keys", True)
    config.set("topn_strategy", "lexsort")
    full = _rows_in_order(sort_chunk(chunk, sort_keys, limit=37),
                          ["k", "payload"])
    config.set("topn_strategy", strategy)
    ctrs = {}
    out = sort_chunk(chunk, sort_keys, limit=37, counters=ctrs)
    got = _rows_in_order(out, ["k", "payload"])

    assert got == full
    # the threshold path SHRINKS the output capacity and reports pruning
    assert out.capacity == pad_capacity(37) < chunk.capacity
    assert int(ctrs["topn_rows_pruned"]) == n - 37


def test_topn_limit_beyond_live_rows():
    chunk = HostTable.from_pydict({"k": [3, 1, 2]}).to_chunk()
    chunk = _with_int_bounds(chunk, {"k": (1, 3)})
    out = sort_chunk(chunk, ((col("k"), True, False),), limit=2000)
    assert _rows_in_order(out, ["k"]) == [(1,), (2,), (3,)]


def _rank_catalog(rng, n=4000):
    cat = Catalog()
    cat.register("t", HostTable.from_pydict({
        "p": [int(x) for x in rng.integers(0, 23, n)],
        "v": [float(x) for x in rng.normal(size=n)],
    }))
    return cat


RANK_TOPN_Q = """
select * from (
  select p, v, rank() over (partition by p order by v desc) rk from t
) x where rk <= 5 order by p, v desc, rk limit 10000
"""


def test_window_topn_rewrite_matches_unrewritten():
    rng = np.random.default_rng(11)
    cat = _rank_catalog(rng)

    config.set("enable_window_topn", False)
    base = Session(cat).sql(RANK_TOPN_Q).rows()
    config.set("enable_window_topn", True)
    s = Session(cat)
    got = s.sql(RANK_TOPN_Q).rows()
    assert got == base
    assert len(got) >= 23 * 5  # every partition keeps its (tied) top 5

    # the rewrite fired; between the pre-sort threshold filter and the
    # in-window rank mask, the dropped rows land in the profile counters
    prof = s.last_profile
    pruned = sum(
        prof.counters.get(nm, (0,))[0]
        for nm in ("window_topn_pruned", "window_topn_prefiltered"))
    assert pruned > 0
    assert "topn=5" in s.sql("explain " + RANK_TOPN_Q)


DENSE_TOPN_Q = """
select * from (
  select p, v, dense_rank() over (partition by p order by v desc) dr from d
) x where dr <= 2 order by p, v desc limit 1000
"""


def test_window_topn_dense_rank_duplicates():
    # dense_rank counts DISTINCT order keys: with scores [10,10,9] and
    # dense_rank()<=2 the 9-row must survive — a per-partition k-th ROW
    # threshold (10) would drop it before the window ever ranks it
    cat = Catalog()
    cat.register("d", HostTable.from_pydict({
        "p": [0, 0, 0, 0, 1, 1, 1],
        "v": [10, 10, 9, 8, 7, 7, 6],
    }))
    config.set("enable_window_topn", False)
    base = Session(cat).sql(DENSE_TOPN_Q).rows()
    config.set("enable_window_topn", True)
    got = Session(cat).sql(DENSE_TOPN_Q).rows()
    assert got == base
    assert (0, 9, 2) in got and (1, 6, 2) in got


def test_window_topn_coresident_funcs_unpruned():
    # the analyzer merges every window func sharing (partition, order)
    # into one LWindow; lead() on a rank-limited node reads rows past
    # rank k, so the pre-sort prefilter must stand down (the exact
    # in-window mask still applies) and surviving rows keep the values
    # computed over the FULL partition
    rng = np.random.default_rng(5)
    cat = _rank_catalog(rng, n=800)
    q = """
    select * from (
      select p, v,
             rank() over (partition by p order by v desc) rk,
             lead(v, 1) over (partition by p order by v desc) nxt,
             sum(v) over (partition by p order by v desc) run
      from t
    ) x where rk <= 3 order by p, v desc limit 10000
    """
    config.set("enable_window_topn", False)
    base = Session(cat).sql(q).rows()
    config.set("enable_window_topn", True)
    got = Session(cat).sql(q).rows()
    assert got == base
    # lead() at the last kept rank must see the (filtered-out) rank-4 row
    assert any(r[3] is not None for r in got)


def test_window_topn_prefilter_nan_scores():
    from starrocks_tpu.ops.window import window_topn_prefilter

    nan = float("nan")
    chunk = HostTable.from_pydict({
        "p": [0, 0, 0, 0, 1, 1],
        "v": [5.0, 4.0, 3.0, nan, 1.0, nan],
    }).to_chunk()
    chunk = _with_int_bounds(chunk, {"p": (0, 1)})
    pre = window_topn_prefilter(
        chunk, (col("p"),), ((col("v"), False, False),), 2)
    assert pre is not None
    keep = np.asarray(pre[0])[:6]
    # partition 0: top-2 by v desc = {5,4}; 3 and the NaN row (the sort
    # places NaN last in either direction) fall past the threshold.
    # partition 1 has fewer than k non-NaN rows: its NaN row ranks 2 and
    # must survive, not fail a NaN-poisoned `>= kth` compare
    assert keep.tolist() == [True, True, False, False, True, True]

    # >= k NaN scores in one partition must not poison the k-th key
    # (a NaN threshold would drop the whole partition)
    c2 = HostTable.from_pydict({"p": [0, 0, 0], "v": [nan, nan, nan]}
                               ).to_chunk()
    c2 = _with_int_bounds(c2, {"p": (0, 0)})
    pre2 = window_topn_prefilter(
        c2, (col("p"),), ((col("v"), True, False),), 2)
    assert pre2 is not None
    assert np.asarray(pre2[0])[:3].all()


def test_order_by_limit_lowers_with_a_sort_scope():
    """What timed the sort from the host (`enable_sort_timing`, a callback
    on each side of it) is gone: the sort now carries its name into a
    profiler trace, `sr.sort.<n>/sort` around the top-k itself."""
    from lowering import SCOPED, lowered_text, scope_paths
    from starrocks_tpu.ops.common import PHASES

    rng = np.random.default_rng(3)
    cat = _rank_catalog(rng, n=2000)
    s = Session(cat)
    r = s.sql("select p, v from t order by p, v limit 50")
    assert "sort_ms" not in r.profile.counters
    text = lowered_text(s, r)
    assert "sr.sort.0/sort" in scope_paths(text, PHASES)
    # whichever way this key sorts (packed top-k, argsort or lexsort), the
    # sort itself is called from inside the phase
    in_sort = [p for p in SCOPED.findall(text) if "/sr.sort.0/sort/" in p]
    assert any(re.search(r"lexsort|argsort|top_k", p) for p in in_sort), in_sort
