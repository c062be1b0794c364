"""Global runtime-filter framework tests: bloom-bitset probe filters,
min/max edge semantics, cross-shard merges, and two-phase scan pruning
(reference: be/src/exec_primitive/runtime_filter/ + the global merge in
orchestration/runtime_filter_worker.h)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from starrocks_tpu import types as T
from starrocks_tpu.column import HostTable
from starrocks_tpu.column.column import Chunk, Field, Schema
from starrocks_tpu.exprs.ir import Col
from starrocks_tpu.ops.join import bloom_filter_mask, runtime_filter_mask
from starrocks_tpu.parallel.mesh import make_mesh, shard_map
from starrocks_tpu.runtime.config import config
from starrocks_tpu.runtime.session import Session
from starrocks_tpu.sql.physical import LUT_JOIN_MAX_RANGE, bloom_rf_bits
from starrocks_tpu.storage.catalog import Catalog


def _key_chunk(keys, valid=None):
    keys = jnp.asarray(np.asarray(keys, dtype=np.int64))
    v = None if valid is None else jnp.asarray(np.asarray(valid, dtype=bool))
    return Chunk(
        Schema((Field("k", T.BIGINT, valid is not None),)),
        (keys,), (v,), None,
    )


@pytest.fixture()
def rf_strategy_reset():
    old = config.get("runtime_filter_strategy")
    yield
    config.set("runtime_filter_strategy", old)


# --- min/max edge semantics --------------------------------------------------


def test_minmax_rf_all_null_build_is_all_false():
    """All-NULL build side: bmin (I64MAX) > bmax (I64MIN), so the probe
    mask is ALL-FALSE — the intended INNER/LEFT-SEMI semantics (an empty
    build key set matches nothing). A refactor flipping the inverted range
    into an all-true mask would break the probe compaction that trusts the
    mask to be a SUBSET of true matches."""
    probe = _key_chunk(np.arange(16))
    build = _key_chunk(np.arange(4), valid=np.zeros(4, dtype=bool))
    mask = runtime_filter_mask(probe, build, (Col("k"),), (Col("k"),))
    assert not bool(jnp.any(mask))


def test_minmax_rf_dead_build_rows_excluded_from_bounds():
    """Dead (unselected) build rows must not widen the min/max bounds."""
    build = Chunk(
        Schema((Field("k", T.BIGINT, False),)),
        (jnp.asarray(np.array([50, 60, 999999], dtype=np.int64)),),
        (None,),
        jnp.asarray(np.array([True, True, False])),
    )
    probe = _key_chunk(np.array([40, 50, 60, 70, 999999]))
    mask = np.asarray(runtime_filter_mask(
        probe, build, (Col("k"),), (Col("k"),)))
    assert mask.tolist() == [False, True, True, False, False]


# --- bloom property: never a false negative ----------------------------------


@pytest.mark.parametrize("n_build,n_probe,key_range,bits", [
    (100, 5000, 1 << 16, 4096),
    (500, 8000, 1 << 40, 8192),
    (2000, 4000, 1 << 62, 1 << 15),
    (50, 1000, 1000, 4096),  # dense narrow range
])
def test_bloom_rf_never_false_negative(n_build, n_probe, key_range, bits):
    rng = np.random.default_rng(n_build + n_probe)
    build_keys = rng.choice(key_range, size=n_build, replace=False)
    probe_keys = rng.integers(0, key_range, size=n_probe)
    # guarantee real matches exist
    probe_keys[:: max(n_probe // n_build, 1)] = rng.choice(
        build_keys, size=len(probe_keys[:: max(n_probe // n_build, 1)]))
    mask = np.asarray(bloom_filter_mask(
        _key_chunk(probe_keys), _key_chunk(build_keys),
        (Col("k"),), (Col("k"),), bits=bits,
    ))
    in_build = np.isin(probe_keys, build_keys)
    # every probe row with a matching build key MUST survive
    assert bool(np.all(mask[in_build])), "bloom RF false-negatived a match"
    # and the filter actually filters: most non-matching rows drop
    non_match = int((~in_build).sum())
    if non_match > 100:
        kept = int((mask & ~in_build).sum())
        assert kept < non_match * 0.5, (kept, non_match)


def test_bloom_rf_null_probe_keys_drop():
    probe = _key_chunk(np.array([1, 2, 3, 4]),
                       valid=np.array([True, False, True, False]))
    build = _key_chunk(np.array([1, 2, 3, 4]))
    mask = np.asarray(bloom_filter_mask(
        probe, build, (Col("k"),), (Col("k"),), bits=4096))
    assert mask.tolist() == [True, False, True, False]


def test_bloom_rf_bits_sizing():
    bits, exactish = bloom_rf_bits(1000.0, 1 << 23)
    assert bits >= 8 * 1000 and bits & (bits - 1) == 0 and exactish
    # capped sizing is no longer near-exact
    bits, exactish = bloom_rf_bits(100_000.0, 1 << 17)
    assert bits == 1 << 17 and not exactish
    # hopeless (<1 bit/key under the cap): no filter at all
    assert bloom_rf_bits(1e9, 1 << 20) is None


# --- cross-shard merge (the global-RF collective) ----------------------------


def _assert_no_min_max_allreduce(sharded_step, *args):
    """The cross-shard merges must not lower to pmin/pmax: on a v5e the
    compiler refuses a 64-bit min/max all-reduce and a four-chip host
    returned wrong lanes for the 8-bit one (TPC-H Q3 lost rows to it)."""
    text = str(jax.make_jaxpr(sharded_step)(*args))
    assert "pmax" not in text and "pmin" not in text


def test_bloom_rf_cross_shard_merge_keeps_remote_matches(eight_devices):
    """Sharded build: each shard holds a DIFFERENT key subset. The bitsets
    must OR-merge across shards so a probe row whose match lives on a
    remote shard still survives on every shard."""
    mesh = make_mesh(8)
    rng = np.random.default_rng(7)
    per_shard = 32
    build_keys = rng.choice(1 << 40, size=8 * per_shard, replace=False)
    probe_keys = np.concatenate(
        [build_keys, rng.integers(0, 1 << 40, size=512)])

    def step(bk_local, pk_all):
        build = Chunk(Schema((Field("k", T.BIGINT, False),)), (bk_local,),
                      (None,), None)
        probe = Chunk(Schema((Field("k", T.BIGINT, False),)), (pk_all,),
                      (None,), None)
        return bloom_filter_mask(probe, build, (Col("k"),), (Col("k"),),
                                 axis="d", bits=8192)

    sharded = shard_map(step, mesh=mesh,
                        in_specs=(P("d"), P()), out_specs=P("d"))
    _assert_no_min_max_allreduce(
        sharded, jnp.asarray(build_keys), jnp.asarray(probe_keys))
    fn = jax.jit(sharded)
    mask = np.asarray(fn(
        jnp.asarray(build_keys), jnp.asarray(probe_keys)
    )).reshape(8, len(probe_keys))
    # EVERY shard keeps EVERY matching probe row, including rows whose
    # build key lives on a different shard
    assert bool(mask[:, : len(build_keys)].all()), (
        "cross-shard merge lost a remote-shard match")
    # identical merged bitset on every shard -> identical masks
    assert bool((mask == mask[0]).all())


def test_minmax_rf_cross_shard_bounds(eight_devices):
    """Sharded build bounds merge across shards: the global range covers
    every shard's keys even though each shard sees a narrow local range."""
    mesh = make_mesh(8)
    build_keys = np.arange(8 * 16, dtype=np.int64) * 1000  # 0..127000
    probe_keys = np.array([0, 500, 127000, 127001, -5], dtype=np.int64)

    def step(bk_local, pk_all):
        build = Chunk(Schema((Field("k", T.BIGINT, False),)), (bk_local,),
                      (None,), None)
        probe = Chunk(Schema((Field("k", T.BIGINT, False),)), (pk_all,),
                      (None,), None)
        return runtime_filter_mask(probe, build, (Col("k"),), (Col("k"),),
                                   axis="d")

    sharded = shard_map(step, mesh=mesh,
                        in_specs=(P("d"), P()), out_specs=P("d"))
    _assert_no_min_max_allreduce(
        sharded, jnp.asarray(build_keys), jnp.asarray(probe_keys))
    fn = jax.jit(sharded)
    mask = np.asarray(fn(
        jnp.asarray(build_keys), jnp.asarray(probe_keys)
    )).reshape(8, len(probe_keys))
    assert mask[0].tolist() == [True, True, True, False, False]
    assert bool((mask == mask[0]).all())


# --- SQL level: bloom engages where the dense range cannot -------------------


def _wide_key_catalog(n_fact=20_000, n_dim=200, seed=0):
    """Join keys sparse over a 2^40 range — far past LUT_JOIN_MAX_RANGE and
    DENSE_RF_MAX_RANGE, so the dense-bitmap/LUT paths cannot engage and
    before this round the probe only got the weak min/max filter."""
    rng = np.random.default_rng(seed)
    keys = rng.choice(1 << 40, size=n_fact, replace=False).astype(np.int64)
    assert int(keys.max() - keys.min()) > LUT_JOIN_MAX_RANGE
    dim_keys = rng.choice(keys, size=n_dim, replace=False)
    cat = Catalog()
    cat.register("fact", HostTable.from_pydict({
        "k": keys, "v": np.arange(n_fact, dtype=np.int64)}))
    cat.register("dim", HostTable.from_pydict({
        "k": dim_keys.astype(np.int64),
        "w": np.ones(n_dim, dtype=np.int64)}), unique_keys=[("k",)])
    return cat


def test_bloom_rf_sql_wide_keys_prunes_and_matches_off(rf_strategy_reset):
    q = ("SELECT sum(f.v) AS sv, count(*) AS c "
         "FROM fact f JOIN dim d ON f.k = d.k")
    s = Session(_wide_key_catalog())
    s.sql("SET runtime_filter_strategy='bloom'")
    r_bloom = s.sql(q).rows()
    ctrs = {k: v for k, (v, _) in s.last_profile.counters.items()}
    assert ctrs.get("rf_rows_pruned", 0) > 0, ctrs
    assert ctrs.get("rf_bloom_bits", 0) > 0, ctrs
    s.sql("SET runtime_filter_strategy='off'")
    r_off = s.sql(q).rows()
    assert "rf_rows_pruned" not in s.last_profile.counters
    assert r_bloom == r_off
    # auto also picks bloom here (dense range unavailable) and agrees
    s.sql("SET runtime_filter_strategy='auto'")
    assert s.sql(q).rows() == r_off
    assert s.last_profile.counters["rf_rows_pruned"][0] > 0


def test_bloom_rf_pushdown_below_probe_filter_chain(rf_strategy_reset):
    """A probe-side WHERE leaves an LFilter chain over the scan; the RF
    mask applies at the chain BOTTOM (pushdown) and results still match
    strategy='off' exactly."""
    q = ("SELECT sum(f.v) AS sv, count(*) AS c "
         "FROM fact f JOIN dim d ON f.k = d.k WHERE f.v % 3 = 0")
    s = Session(_wide_key_catalog(seed=3))
    s.sql("SET runtime_filter_strategy='bloom'")
    r_bloom = s.sql(q).rows()
    assert s.last_profile.counters["rf_rows_pruned"][0] > 0
    s.sql("SET runtime_filter_strategy='off'")
    assert s.sql(q).rows() == r_bloom


def test_minmax_strategy_still_correct(rf_strategy_reset):
    q = ("SELECT sum(f.v) AS sv, count(*) AS c "
         "FROM fact f JOIN dim d ON f.k = d.k")
    s = Session(_wide_key_catalog(seed=5))
    s.sql("SET runtime_filter_strategy='minmax'")
    r_mm = s.sql(q).rows()
    s.sql("SET runtime_filter_strategy='off'")
    assert s.sql(q).rows() == r_mm


# --- two-phase scan-level pruning --------------------------------------------


def test_scan_rf_prunes_segments_and_matches_off(tmp_path, rf_strategy_reset):
    """Multi-segment stored probe + selective dimension build: build key
    bounds evaluated on host numpy prune probe parquet files via their
    zonemaps (rf_segments_pruned > 0) with unchanged query results."""
    s = Session(data_dir=str(tmp_path))
    s.sql("CREATE TABLE fact (k BIGINT, v BIGINT)")
    s.sql("CREATE TABLE dim (k BIGINT, attr VARCHAR)")
    # 4 rowsets with disjoint key ranges -> 4 parquet files with zonemaps
    for base in (0, 1000, 2000, 3000):
        vals = ", ".join(f"({base + i}, {i})" for i in range(100))
        s.sql(f"INSERT INTO fact VALUES {vals}")
    s.sql("INSERT INTO dim VALUES (2005, 'x'), (2010, 'x'), (500, 'y')")
    q = ("SELECT sum(f.v) AS sv, count(*) AS c "
         "FROM fact f JOIN dim d ON f.k = d.k WHERE d.attr = 'x'")
    r_auto = s.sql(q).rows()
    ctrs = {k: v for k, (v, _) in s.last_profile.counters.items()}
    assert ctrs.get("rf_segments_pruned", 0) > 0, ctrs
    s.sql("SET runtime_filter_strategy='off'")
    r_off = s.sql(q).rows()
    assert "rf_segments_pruned" not in s.last_profile.counters
    assert r_auto == r_off == [(15, 2)]


def test_scan_rf_empty_build_prunes_everything(tmp_path, rf_strategy_reset):
    """A build-side filter matching NOTHING yields the empty-build sentinel
    bounds: every probe segment prunes and the join returns no rows."""
    s = Session(data_dir=str(tmp_path))
    s.sql("CREATE TABLE fact (k BIGINT, v BIGINT)")
    s.sql("CREATE TABLE dim (k BIGINT, attr VARCHAR)")
    for base in (0, 1000):
        vals = ", ".join(f"({base + i}, {i})" for i in range(50))
        s.sql(f"INSERT INTO fact VALUES {vals}")
    s.sql("INSERT INTO dim VALUES (10, 'y')")
    q = ("SELECT count(*) AS c FROM fact f JOIN dim d ON f.k = d.k "
         "WHERE d.attr = 'nope'")
    assert s.sql(q).rows() == [(0,)]
    ctrs = {k: v for k, (v, _) in s.last_profile.counters.items()}
    assert ctrs.get("rf_segments_pruned", 0) == 2, ctrs


def test_scan_rf_respects_dml_invalidation(tmp_path, rf_strategy_reset):
    """Growing the dimension AFTER a pruned run must widen the bounds on
    the next run — stale pruned snapshots would silently drop rows."""
    s = Session(data_dir=str(tmp_path))
    s.sql("CREATE TABLE fact (k BIGINT, v BIGINT)")
    s.sql("CREATE TABLE dim (k BIGINT, attr VARCHAR)")
    for base in (0, 1000, 2000):
        vals = ", ".join(f"({base + i}, {i})" for i in range(50))
        s.sql(f"INSERT INTO fact VALUES {vals}")
    s.sql("INSERT INTO dim VALUES (2005, 'x')")
    q = ("SELECT count(*) AS c FROM fact f JOIN dim d ON f.k = d.k "
         "WHERE d.attr = 'x'")
    assert s.sql(q).rows() == [(1,)]
    s.sql("INSERT INTO dim VALUES (5, 'x')")  # key in a previously-pruned file
    assert s.sql(q).rows() == [(2,)]
