"""Next-generation join engine tests (round 13).

Three families:

1. skew-aware hybrid hash join (runtime/batched.py hybrid_partitions /
   execute_hybrid_join): Zipfian/heavy-hitter key distributions vs the
   pandas oracle across INNER/LEFT/SEMI/ANTI, the one-hot-key-never-
   forces-a-full-spill invariant, grace A/B equality, and dict-encoded
   key fallback (string keys can't host-partition — the plan must keep
   the in-HBM path and stay dictionary-aligned);
2. Free-Join-style multiway fusion (sql/physical.multiway_join_chain /
   emit_multiway): star + snowflake shapes vs the oracle, off-A/B
   equality, fallback on non-unique builds, and the plan checker's
   independent re-verification of fused invariants;
3. the sorted unique-join kernel (ops/join.hash_join_unique: build
   argsort + searchsorted probe) on sparse wide-range keys that defeat the
   LUT path, INNER/LEFT/SEMI/ANTI against pandas.
"""

import numpy as np
import pandas as pd
import pytest

from starrocks_tpu.column import HostTable
from starrocks_tpu.runtime import batched
from starrocks_tpu.runtime.config import config
from starrocks_tpu.runtime.session import Session
from starrocks_tpu.storage.catalog import Catalog


def _counters(session) -> dict:
    out = {}

    def walk(p):
        out.update({k: v for k, (v, _) in p.counters.items()})
        for c in p.children:
            walk(c)

    walk(session.last_profile)
    return out


def _zipf_keys(rng, n, domain, a=1.1):
    """Zipfian keys clipped into [0, domain) — a realistic heavy tail."""
    z = rng.zipf(a, n)
    return np.minimum(z - 1, domain - 1).astype(np.int64)


def _skew_catalog(rng, n_probe=60_000, n_build=24_000, hot_frac=0.5,
                  domain=3_000, probe_domain=None, build_nulls=False):
    """Probe + skewed build: one heavy-hitter key owns `hot_frac` of the
    build side. The build is the SMALLER relation so the optimizer keeps
    it on the build (right) side of the join."""
    bk = rng.integers(0, domain, n_build)
    bk[: int(n_build * hot_frac)] = 7
    rng.shuffle(bk)
    pk = rng.integers(0, probe_domain or int(domain * 1.2), n_probe)
    cat = Catalog()
    cat.register("probe", HostTable.from_pydict({
        "k": list(pk.astype(int)),
        "v": list(rng.integers(0, 100, n_probe).astype(int)),
    }))
    bcols = {"k": list(bk.astype(int)),
             "w": list(rng.integers(0, 100, n_build).astype(int))}
    bt = HostTable.from_pydict(bcols)
    if build_nulls:
        bt.valids["k"] = np.arange(n_build) % 7 != 0
    cat.register("build", bt)
    dp = pd.DataFrame({"k": pk, "v": cat.get_table("probe").table.arrays["v"]})
    db = cat.get_table("build").table.to_pandas()
    return cat, dp, db


@pytest.fixture
def spill_knobs():
    old_t = config.get("batch_rows_threshold")
    old_b = config.get("spill_batch_rows")
    config.set("batch_rows_threshold", 8_192)
    config.set("spill_batch_rows", 8_192)
    yield
    config.set("batch_rows_threshold", old_t)
    config.set("spill_batch_rows", old_b)
    config.set("join_hybrid_strategy", "auto")


# --- 1. skew-aware hybrid hash join ------------------------------------------


def test_hybrid_inner_skewed_vs_oracle_and_grace(spill_knobs):
    rng = np.random.default_rng(11)
    cat, dp, db = _skew_catalog(rng)
    s = Session(cat)
    q = ("SELECT sum(v + w) sv, count(*) c FROM probe, build "
         "WHERE probe.k = build.k")
    got = s.sql(q).rows()
    cs = _counters(s)
    assert cs.get("join_skew_keys", 0) >= 1, cs
    assert "join_spilled_partitions" in cs
    m = dp.merge(db, on="k")
    assert [(int(a), int(b)) for a, b in got] == [
        (int((m.v + m.w).sum()), len(m))]
    # legacy grace agrees bit-for-bit
    config.set("join_hybrid_strategy", "grace")
    assert s.sql(q).rows() == got
    assert batched.SPILL_PARTS_LIVE.value == 0


def test_hybrid_one_hot_key_no_full_spill(spill_knobs):
    """THE skew invariant: with one heavy-hitter key and a cold remainder
    that fits the batch budget, the hybrid join spills NOTHING — the hot
    key rides the broadcast lane and the cold build stays resident. The
    legacy grace path partitioned (and streamed) everything."""
    rng = np.random.default_rng(13)
    # cold build = 6k rows (< 8192 budget); hot key owns another 18k rows
    cat, dp, db = _skew_catalog(rng, n_build=24_000, hot_frac=0.75)
    s = Session(cat)
    q = "SELECT count(*) c, sum(w) sw FROM probe, build WHERE probe.k = build.k"
    got = s.sql(q).rows()
    cs = _counters(s)
    assert cs.get("join_skew_keys", 0) >= 1, cs
    assert cs.get("join_spilled_partitions", -1) == 0, cs
    assert cs.get("join_resident_partitions", 0) >= 1, cs
    m = dp.merge(db, on="k")
    assert [(int(a), int(b)) for a, b in got] == [(len(m), int(m.w.sum()))]
    assert batched.SPILL_PARTS_LIVE.value == 0


def test_hybrid_left_outer_zipf_vs_oracle(spill_knobs):
    """Zipfian PROBE keys against a near-unique build (the FK->dim shape:
    probe skew is absorbed by probe-slice streaming; build dup factor <= 2
    keeps the join output bounded at ~2x probe rows)."""
    rng = np.random.default_rng(17)
    n, m = 30_000, 15_000
    pk = _zipf_keys(rng, n, 20_000)
    bk = np.concatenate([np.arange(10_000), rng.integers(0, 20_000, m - 10_000)])
    cat = Catalog()
    cat.register("probe", HostTable.from_pydict({
        "k": list(pk.astype(int)), "v": list(range(n))}))
    cat.register("build", HostTable.from_pydict({
        "k": list(bk.astype(int)),
        "w": list(rng.integers(0, 50, m).astype(int))}))
    s = Session(cat)
    q = ("SELECT count(*) c, count(w) cw, sum(v) sv, sum(w) sw "
         "FROM probe LEFT JOIN build ON probe.k = build.k")
    got = s.sql(q).rows()
    dfp = pd.DataFrame({"k": pk, "v": np.arange(n)})
    dfb = cat.get_table("build").table.to_pandas()
    mg = dfp.merge(dfb, on="k", how="left")
    exp = [(len(mg), int(mg.w.notna().sum()), int(mg.v.sum()),
            int(mg.w.sum()))]
    assert [(int(a), int(b), int(c), int(d)) for a, b, c, d in got] == exp
    config.set("join_hybrid_strategy", "grace")
    assert s.sql(q).rows() == got


def test_hybrid_semi_anti_vs_oracle(spill_knobs):
    rng = np.random.default_rng(19)
    cat, dp, db = _skew_catalog(rng, n_probe=40_000, n_build=20_000)
    s = Session(cat)
    semi = ("SELECT count(*) c, sum(v) sv FROM probe WHERE k IN "
            "(SELECT k FROM build)")
    anti = ("SELECT count(*) c, sum(v) sv FROM probe WHERE k NOT IN "
            "(SELECT k FROM build) AND k IS NOT NULL")
    got_semi = s.sql(semi).rows()
    got_anti = s.sql(anti).rows()
    member = dp.k.isin(set(db.k))
    exp_semi = [(int(member.sum()), int(dp.v[member].sum()))]
    exp_anti = [(int((~member).sum()), int(dp.v[~member].sum()))]
    assert [(int(a), int(b)) for a, b in got_semi] == exp_semi
    assert [(int(a), int(b)) for a, b in got_anti] == exp_anti
    config.set("join_hybrid_strategy", "grace")
    assert s.sql(semi).rows() == got_semi
    assert s.sql(anti).rows() == got_anti


def test_hybrid_null_build_keys(spill_knobs):
    """NULL join keys never match (SQL equality): routing NULL-carrying
    rows through the lanes must not invent matches."""
    rng = np.random.default_rng(23)
    cat, dp, db = _skew_catalog(rng, n_probe=30_000, n_build=15_000,
                                build_nulls=True)
    s = Session(cat)
    q = "SELECT count(*) c FROM probe, build WHERE probe.k = build.k"
    got = s.sql(q).rows()
    bk = cat.get_table("build").table
    live = pd.DataFrame({"k": np.asarray(bk.arrays["k"])[bk.valids["k"]]})
    exp = [(len(dp.merge(live, on="k")),)]
    assert [(int(a),) for (a,) in got] == exp


def test_hybrid_string_keys_fall_back_dict_aligned(spill_knobs):
    """Dict-encoded string keys can't host-partition (the hybrid/grace
    matcher requires int64-able keys): the plan keeps the in-HBM join,
    whose pack_key_pair aligns the two sides' dictionaries — equal strings
    must match even though their per-table codes differ."""
    rng = np.random.default_rng(29)
    words1 = [f"w{i:04d}" for i in range(400)]
    words2 = [f"w{i:04d}" for i in range(200, 600)]  # shifted code space
    n, m = 30_000, 12_000
    cat = Catalog()
    cat.register("probe", HostTable.from_pydict({
        "k": [words1[i] for i in rng.integers(0, 400, n)],
        "v": list(range(n))}))
    cat.register("build", HostTable.from_pydict({
        "k": [words2[i] for i in rng.integers(0, 400, m)],
        "w": list(rng.integers(0, 9, m).astype(int))}))
    s = Session(cat)
    q = "SELECT count(*) c, sum(v) sv FROM probe, build WHERE probe.k = build.k"
    got = s.sql(q).rows()
    dp = cat.get_table("probe").table.to_pandas()
    db = cat.get_table("build").table.to_pandas()
    mg = dp.merge(db, on="k")
    assert [(int(a), int(b)) for a, b in got] == [
        (len(mg), int(mg.v.sum()))]


# --- 2. Free-Join multiway fusion --------------------------------------------


def _star_catalog(rng, n=25_000):
    cat = Catalog()
    cat.register("fact", HostTable.from_pydict({
        "fk1": list(rng.integers(0, 100, n).astype(int)),
        "fk2": list(rng.integers(0, 50, n).astype(int)),
        "v": list(rng.integers(0, 1000, n).astype(int)),
    }))
    cat.register("d1", HostTable.from_pydict({
        "k1": list(range(100)),
        "a": list(rng.integers(0, 10, 100).astype(int)),
        "snow": list(rng.integers(0, 30, 100).astype(int)),
    }), unique_keys=[("k1",)])
    cat.register("d2", HostTable.from_pydict({
        "k2": list(range(50)),
        "b": list(rng.integers(0, 10, 50).astype(int)),
    }), unique_keys=[("k2",)])
    cat.register("d3", HostTable.from_pydict({
        "k3": list(range(30)),
        "c": list(rng.integers(0, 5, 30).astype(int)),
    }), unique_keys=[("k3",)])
    return cat


STAR_Q = ("SELECT d1.a, sum(v) sv, count(*) c FROM fact, d1, d2, d3 "
          "WHERE fact.fk1 = d1.k1 AND fact.fk2 = d2.k2 AND d1.snow = d3.k3 "
          "AND d2.b < 8 AND d3.c < 4 GROUP BY d1.a ORDER BY d1.a")


def _star_oracle(cat):
    f = cat.get_table("fact").table.to_pandas()
    t1 = cat.get_table("d1").table.to_pandas()
    t2 = cat.get_table("d2").table.to_pandas()
    t3 = cat.get_table("d3").table.to_pandas()
    m = (f.merge(t1, left_on="fk1", right_on="k1")
          .merge(t2, left_on="fk2", right_on="k2")
          .merge(t3, left_on="snow", right_on="k3"))
    m = m[(m.b < 8) & (m.c < 4)]
    g = m.groupby("a").agg(sv=("v", "sum"), c=("v", "size")).reset_index()
    return [(int(r.a), int(r.sv), int(r.c))
            for r in g.sort_values("a").itertuples()]


def test_multiway_star_snowflake_vs_oracle_and_off():
    rng = np.random.default_rng(31)
    cat = _star_catalog(rng)
    s = Session(cat)
    got = s.sql(STAR_Q).rows()
    cs = _counters(s)
    # 3 fused levels: two star arms + one snowflake arm (d1.snow -> d3)
    assert cs.get("join_multiway_hits") == 3, cs
    assert [(int(a), int(sv), int(c)) for a, sv, c in got] == _star_oracle(cat)
    s.sql("SET join_multiway_strategy = 'off'")
    try:
        assert s.sql(STAR_Q).rows() == got
        assert "join_multiway_hits" not in _counters(s)
    finally:
        config.set("join_multiway_strategy", "auto")


def test_multiway_requires_unique_builds():
    """A dimension with DUPLICATE keys is not LUT-eligible: the region
    must fall back to binary joins (which expand duplicates correctly)."""
    rng = np.random.default_rng(37)
    n = 8_000
    cat = Catalog()
    cat.register("fact", HostTable.from_pydict({
        "fk1": list(rng.integers(0, 40, n).astype(int)),
        "fk2": list(rng.integers(0, 20, n).astype(int)),
        "v": list(rng.integers(0, 100, n).astype(int))}))
    # d1 declared unique; dup carries DUPLICATE join keys (2 rows per key)
    cat.register("d1", HostTable.from_pydict({
        "k1": list(range(40)),
        "a": list(rng.integers(0, 5, 40).astype(int))}),
        unique_keys=[("k1",)])
    cat.register("dup", HostTable.from_pydict({
        "k2": [i % 20 for i in range(40)],
        "b": list(rng.integers(0, 5, 40).astype(int))}))
    s = Session(cat)
    q = ("SELECT sum(v) sv, count(*) c, sum(b) sb FROM fact, d1, dup "
         "WHERE fact.fk1 = d1.k1 AND fact.fk2 = dup.k2")
    got = s.sql(q).rows()
    assert "join_multiway_hits" not in _counters(s)
    f = cat.get_table("fact").table.to_pandas()
    t1 = cat.get_table("d1").table.to_pandas()
    t2 = cat.get_table("dup").table.to_pandas()
    m = (f.merge(t1, left_on="fk1", right_on="k1")
          .merge(t2, left_on="fk2", right_on="k2"))
    assert [(int(a), int(b), int(c)) for a, b, c in got] == [
        (int(m.v.sum()), len(m), int(m.b.sum()))]


def test_multiway_plan_checker_flags_relaxed_eligibility(monkeypatch):
    """check_multiway re-verifies fused invariants INDEPENDENTLY: relax
    the compiler-side eligibility (drop the uniqueness proof) and the
    checker must flag the non-unique build the fusion would mis-join."""
    from starrocks_tpu.analysis import plan_check
    from starrocks_tpu.sql import physical
    from starrocks_tpu.sql.optimizer import optimize
    from starrocks_tpu.sql.parser import parse
    from starrocks_tpu.sql.analyzer import Analyzer

    rng = np.random.default_rng(41)
    n = 4_000
    cat = Catalog()
    cat.register("fact", HostTable.from_pydict({
        "fk1": list(rng.integers(0, 40, n).astype(int)),
        "fk2": list(rng.integers(0, 20, n).astype(int)),
        "v": list(rng.integers(0, 100, n).astype(int))}))
    cat.register("d1", HostTable.from_pydict({
        "k1": list(range(40)), "a": list(range(40))}),
        unique_keys=[("k1",)])
    cat.register("dup", HostTable.from_pydict({
        "k2": [i % 20 for i in range(40)], "b": list(range(40))}))

    orig = physical.multiway_level

    def relaxed(p, catalog):
        lev = orig(p, catalog)
        if lev is not None:
            return lev
        # the buggy relaxation under test: accept ANY single-key inner
        # join with a bounded range, skipping the uniqueness proof
        from starrocks_tpu.exprs.ir import Col
        from starrocks_tpu.sql.physical import (
            LUT_JOIN_MAX_RANGE, dense_rf_range, join_equi_keys,
        )
        if not isinstance(p, physical.LJoin) or p.kind != "inner" \
                or p.condition is None:
            return None
        pks, bks, residual = join_equi_keys(p)
        if len(pks) != 1 or residual or not all(
                isinstance(k, Col) for k in (pks[0], bks[0])):
            return None
        rng_ = dense_rf_range(p.left, p.right, pks, bks, catalog,
                              max_range=LUT_JOIN_MAX_RANGE)
        return None if rng_ is None else (pks[0], bks[0], *rng_)

    monkeypatch.setattr(physical, "multiway_level", relaxed)
    q = ("SELECT sum(v) FROM fact, d1, dup "
         "WHERE fact.fk1 = d1.k1 AND fact.fk2 = dup.k2")
    plan = optimize(Analyzer(cat).analyze(parse(q)), cat)
    findings = plan_check.check_multiway(plan, cat)
    assert any("not provably unique" in f.message for f in findings), findings


# --- 3. the sorted unique-join kernel on sparse keys ---------------------------


@pytest.fixture(scope="module")
def sparse_unique():
    rng = np.random.default_rng(43)
    n = 6_000
    # sparse wide-range keys defeat the LUT path, forcing the sorted
    # unique-join kernel; probe keys overlap the build's domain by half
    f = pd.DataFrame({
        "k": ((rng.integers(0, 1200, n) * 1_000_003) % (1 << 40)).astype(
            np.int64),
        "v": rng.integers(0, 50, n).astype(np.int64)})
    d = pd.DataFrame({
        "k": (np.arange(600) * 1_000_003 % (1 << 40)).astype(np.int64),
        "w": rng.integers(0, 5, 600).astype(np.int64)})
    cat = Catalog()
    cat.register("f", HostTable.from_pydict(f.to_dict("list")))
    cat.register("d", HostTable.from_pydict(d.to_dict("list")), unique_keys=[("k",)])
    return Session(cat), f, d


@pytest.mark.parametrize("kind", ["inner", "left", "semi", "anti"])
def test_sorted_unique_join_matches_pandas(sparse_unique, kind):
    s, f, d = sparse_unique
    m = f.merge(d, on="k", how="left", indicator=True)
    hit = m["_merge"] == "both"
    q, want = {
        "inner": ("SELECT count(*) c, sum(v) sv, sum(w) sw FROM f, d "
                  "WHERE f.k = d.k",
                  (int(hit.sum()), int(m.v[hit].sum()), int(m.w[hit].sum()))),
        "left": ("SELECT count(*) c, count(w) cw, sum(v) sv FROM f "
                 "LEFT JOIN d ON f.k = d.k",
                 (len(m), int(hit.sum()), int(m.v.sum()))),
        "semi": ("SELECT count(*) c, sum(v) sv FROM f "
                 "WHERE k IN (SELECT k FROM d)",
                 (int(hit.sum()), int(m.v[hit].sum()))),
        "anti": ("SELECT count(*) c, sum(v) sv FROM f "
                 "WHERE k NOT IN (SELECT k FROM d)",
                 (int((~hit).sum()), int(m.v[~hit].sum()))),
    }[kind]
    assert 0 < int(hit.sum()) < len(m)
    assert s.sql(q).rows() == [want]
