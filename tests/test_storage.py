"""Persistent storage tests: tablet store, edit-log replay, zonemap pruning,
CSV load (reference analog: be/test/storage/)."""

import os

import datetime

import numpy as np
import pytest

from starrocks_tpu import types as T
from starrocks_tpu.column import HostTable
from starrocks_tpu.exprs.ir import Call, Col, Lit
from starrocks_tpu.runtime.session import Session
from starrocks_tpu.storage.store import TabletStore


def test_create_insert_restart_roundtrip(tmp_path):
    d = str(tmp_path / "db")
    s = Session(data_dir=d)
    s.sql("create table t (a int not null, b varchar, c decimal(10,2)) distributed by hash(a) buckets 4")
    s.sql("insert into t values (1, 'x', 1.50), (2, 'y', 2.25), (3, 'x', 0.75)")
    s.sql("insert into t values (4, 'z', 9.99)")
    r = s.sql("select b, sum(c) sc from t group by b order by b")
    assert r.rows() == [("x", 2.25), ("y", 2.25), ("z", 9.99)]

    # restart: a fresh session over the same dir rebuilds the catalog
    s2 = Session(data_dir=d)
    r2 = s2.sql("select b, sum(c) sc from t group by b order by b")
    assert r2.rows() == r.rows()
    # files on disk are bucketed parquet rowsets
    files = os.listdir(os.path.join(d, "t"))
    assert any(f.endswith(".parquet") for f in files)
    assert "manifest.json" in files

    s2.sql("drop table t")
    s3 = Session(data_dir=d)
    with pytest.raises(Exception):
        s3.sql("select * from t")


def test_zonemap_pruning(tmp_path):
    store = TabletStore(str(tmp_path / "z"))
    ht1 = HostTable.from_pydict({"k": np.arange(0, 100), "v": np.arange(100) * 1.0})
    ht2 = HostTable.from_pydict({"k": np.arange(1000, 1100), "v": np.arange(100) * 2.0})
    from starrocks_tpu.column import Schema
    store.create_table("t", ht1.schema, (), 1)
    store.insert("t", ht1)
    store.insert("t", ht2)

    # predicate k > 500 excludes the first rowset by zonemap
    pred = Call("gt", Col("t.k"), Lit(500))
    out = store.load_table("t", predicate=pred)
    assert store.last_scan_stats == {"files": 2, "pruned": 1,
                                 "partition_pruned": 0, "rf_pruned": 0}
    assert out.num_rows == 100
    assert int(out.arrays["k"].min()) == 1000

    # eq inside range: nothing pruned
    out2 = store.load_table("t", predicate=Call("eq", Col("t.k"), Lit(50)))
    assert store.last_scan_stats["pruned"] == 1  # second rowset excluded
    # impossible predicate prunes everything
    out3 = store.load_table("t", predicate=Call("gt", Col("t.k"), Lit(10**6)))
    assert store.last_scan_stats["pruned"] == 2
    assert out3.num_rows == 0


def test_nulls_and_strings_roundtrip(tmp_path):
    d = str(tmp_path / "db2")
    s = Session(data_dir=d)
    s.sql("create table u (a int, b varchar)")
    s.sql("insert into u values (1, 'aa'), (null, 'bb'), (3, null)")
    s2 = Session(data_dir=d)
    rows = s2.sql("select a, b from u order by a nulls first").rows()
    assert rows == [(None, "bb"), (1, "aa"), (3, None)]


def test_csv_load(tmp_path):
    d = str(tmp_path / "db3")
    csv = tmp_path / "data.csv"
    csv.write_text("1,foo,2.5\n2,bar,3.5\n3,foo,4.5\n")
    s = Session(data_dir=d)
    s.sql("create table c (id int, name varchar, amt double)")
    n = s.load_csv("c", str(csv))
    assert n == 3
    r = s.sql("select name, sum(amt) t from c group by name order by name")
    assert r.rows() == [("bar", 3.5), ("foo", 7.0)]


def test_insert_select_persisted(tmp_path):
    d = str(tmp_path / "db4")
    s = Session(data_dir=d)
    s.sql("create table src (a int, b double)")
    s.sql("insert into src values (1, 1.5), (2, 2.5), (3, 3.5)")
    s.sql("create table dst (a int, b double)")
    s.sql("insert into dst select a, b * 2 from src where a >= 2")
    s2 = Session(data_dir=d)
    assert s2.sql("select sum(b) s from dst group by a > 0").rows() == [(12.0,)]


def test_native_kernels():
    from starrocks_tpu import native

    if not native.available():
        pytest.skip("native toolchain unavailable")
    k = np.arange(100000, dtype=np.int64)
    b = native.hash_partition_i64(k, 16)
    counts = np.bincount(b, minlength=16)
    assert counts.min() > 5000  # roughly uniform
    # deterministic + matches the documented splitmix64 formula
    z = k.view(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    z = z ^ (z >> np.uint64(31))
    np.testing.assert_array_equal(b, (z % np.uint64(16)).astype(np.int32))


def test_native_csv_parse(tmp_path):
    from starrocks_tpu import native

    if not native.available():
        pytest.skip("native toolchain unavailable")
    data = b"1,2.5,2020-01-02,hi\n2,,2021-03-04,yo\n"
    cols, masks, n = native.parse_csv(
        data, [native.CSV_INT64, native.CSV_FLOAT64, native.CSV_DATE, native.CSV_STRING]
    )
    assert n == 2
    assert list(cols[0]) == [1, 2]
    assert list(masks[1]) == [True, False]
    assert list(cols[2]) == [18263, 18690]
    assert list(cols[3]) == ["hi", "yo"]


def test_csv_load_native_path(tmp_path):
    d = str(tmp_path / "dbn")
    csv = tmp_path / "n.csv"
    csv.write_text("1,2020-01-02,2.5\n2,2020-01-03,\n")
    s = Session(data_dir=d)
    s.sql("create table n (id int, d date, amt double)")
    assert s.load_csv("n", str(csv)) == 2
    rows = s.sql("select id, d, amt from n order by id").rows()
    assert rows == [(1, "2020-01-02", 2.5), (2, "2020-01-03", None)]


def test_backup_restore(tmp_path):
    from starrocks_tpu.storage.store import backup, restore

    d1, d2, d3 = str(tmp_path / "db"), str(tmp_path / "bk"), str(tmp_path / "rs")
    s = Session(data_dir=d1)
    s.sql("create table t (a int, b varchar, primary key(a))")
    s.sql("insert into t values (1, 'x'), (2, 'y')")
    assert backup(s.store, d2) == 1
    # post-backup writes don't affect the snapshot
    s.sql("insert into t values (3, 'z')")
    assert restore(d2, d3) == 1
    s2 = Session(data_dir=d3)
    assert s2.sql("select a, b from t order by a").rows() == [(1, "x"), (2, "y")]
    # restored store keeps PK semantics
    s2.sql("insert into t values (1, 'X')")
    assert s2.sql("select a, b from t order by a").rows() == [(1, "X"), (2, "y")]
    with pytest.raises(ValueError):
        restore(d2, d3)  # non-empty target rejected


def test_compilation_cache_placement(tmp_path):
    """Both placement rules of the persistent XLA cache, each in a fresh
    process after the whole engine is imported: JAX_COMPILATION_CACHE_DIR
    set -> that directory and no other; unset -> the one normalised fixed
    path <checkout>/.xla_cache."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fixed = os.path.join(repo, ".xla_cache")
    probe = ("import jax, starrocks_tpu, starrocks_tpu.runtime.session; "
             "print(jax.config.jax_compilation_cache_dir)")

    def cache_dir(env_dir):
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env["JAX_PLATFORMS"] = "cpu"
        if env_dir:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        out = subprocess.run([sys.executable, "-c", probe], cwd=repo,
                             env=env, capture_output=True, text=True,
                             timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        return out.stdout.strip().splitlines()[-1]

    assert cache_dir(str(tmp_path)) == str(tmp_path)
    got = cache_dir(None)
    assert got == fixed and got == os.path.normpath(got)


# --- round 3: partitions, compaction, PK delta path --------------------------


def test_range_partition_pruning(tmp_path):
    from starrocks_tpu.runtime.session import Session

    s = Session(data_dir=str(tmp_path))
    s.sql("CREATE TABLE events (id BIGINT, d DATE, v DOUBLE) "
          "PARTITION BY RANGE(d) ("
          " PARTITION p1 VALUES LESS THAN ('2024-01-01'),"
          " PARTITION p2 VALUES LESS THAN ('2024-07-01'),"
          " PARTITION p3 VALUES LESS THAN (MAXVALUE))")
    s.sql("INSERT INTO events VALUES "
          "(1, DATE '2023-05-01', 1.0), (2, DATE '2023-11-30', 2.0),"
          "(3, DATE '2024-02-01', 3.0), (4, DATE '2024-06-30', 4.0),"
          "(5, DATE '2024-12-25', 5.0)")
    parts = s.sql("SHOW PARTITIONS FROM events")
    assert [p[0] for p in parts] == ["p1", "p2", "p3"]
    assert [p[4] for p in parts] == [2, 2, 1]
    # fresh session: replay from manifests; SQL answers stay correct
    s2 = Session(data_dir=str(tmp_path))
    r = s2.sql("SELECT sum(v) FROM events WHERE d >= DATE '2024-08-01'")
    assert r.rows() == [(5.0,)]
    r = s2.sql("SELECT count(*) FROM events WHERE d < DATE '2024-01-01'")
    assert r.rows() == [(2,)]
    # manifest-only partition pruning at the storage read API (the SQL path
    # caches whole tables on device; pruning pays off on loads)
    from starrocks_tpu import types as T
    from starrocks_tpu.exprs.ir import Call, Col, Lit

    days = (datetime.date(2024, 8, 1) - datetime.date(1970, 1, 1)).days
    pred = Call("ge", Col("events.d"), Lit(days, T.DATE))
    out = s2.store.load_table("events", predicate=pred)
    st = s2.store.last_scan_stats
    assert st["partition_pruned"] >= 2, st  # p1+p2 skipped from the manifest
    assert out.num_rows == 1


def test_partition_bound_violation(tmp_path):
    from starrocks_tpu.runtime.session import Session

    s = Session(data_dir=str(tmp_path))
    s.sql("CREATE TABLE b (x BIGINT) PARTITION BY RANGE(x) ("
          " PARTITION p1 VALUES LESS THAN (10))")
    with pytest.raises(Exception, match="partition bound"):
        s.sql("INSERT INTO b VALUES (11)")
    s.sql("INSERT INTO b VALUES (9)")
    assert s.sql("SELECT count(*) FROM b").rows() == [(1,)]


def test_compaction_bounds_file_count(tmp_path):
    import os

    from starrocks_tpu.runtime.config import config
    from starrocks_tpu.runtime.session import Session

    s = Session(data_dir=str(tmp_path))
    s.sql("CREATE TABLE t (k BIGINT, v DOUBLE)")
    for i in range(20):
        s.sql(f"INSERT INTO t VALUES ({i}, {i * 1.5})")
    files = [f for f in os.listdir(tmp_path / "t") if f.endswith(".parquet")]
    trigger = config.get("compaction_trigger_rowsets")
    assert len(files) < trigger + 1, files  # compaction kept it bounded
    r = s.sql("SELECT count(*) c, sum(v) sv FROM t").rows()
    assert r == [(20, sum(i * 1.5 for i in range(20)))]


def test_pk_upsert_delta_path(tmp_path):
    import os

    from starrocks_tpu.runtime.config import config
    from starrocks_tpu.runtime.session import Session

    old = config.get("compaction_trigger_rowsets")
    config.set("compaction_trigger_rowsets", 0)  # isolate the delta path
    try:
        s = Session(data_dir=str(tmp_path))
        s.sql("CREATE TABLE kv (k BIGINT, v VARCHAR, PRIMARY KEY(k))")
        n = 5000
        rows = ", ".join(f"({i}, 'v{i}')" for i in range(n))
        s.sql(f"INSERT INTO kv VALUES {rows}")
        base_bytes = sum(
            os.path.getsize(tmp_path / "kv" / f)
            for f in os.listdir(tmp_path / "kv") if f.endswith(".parquet"))
        # 1% upsert: must write O(delta), not rewrite the table
        up = ", ".join(f"({i}, 'NEW{i}')" for i in range(0, n, 100))
        s.sql(f"INSERT INTO kv VALUES {up}")
        m = s.store.read_manifest("kv")
        assert len(m["rowsets"]) == 2  # base + delta, no rewrite
        delta_files = m["rowsets"][1]["files"]
        delta_bytes = sum(
            os.path.getsize(tmp_path / "kv" / f["file"]) for f in delta_files)
        assert delta_bytes < base_bytes / 10, (delta_bytes, base_bytes)
        assert sum(len(f.get("delvec") or ())
                   for f in m["rowsets"][0]["files"]) == 50
        # reads apply delete vectors; last write wins
        r = s.sql("SELECT count(*) FROM kv").rows()
        assert r == [(n,)]
        r = s.sql("SELECT v FROM kv WHERE k = 200").rows()
        assert r == [("NEW200",)]
        r = s.sql("SELECT v FROM kv WHERE k = 201").rows()
        assert r == [("v201",)]
        # a second upsert hits the DELTA rowset's rows too
        s.sql("INSERT INTO kv VALUES (200, 'NEWER200')")
        assert s.sql("SELECT v FROM kv WHERE k = 200").rows() == [
            ("NEWER200",)]
        assert s.sql("SELECT count(*) FROM kv").rows() == [(n,)]
        # restart: delvecs replay from the manifest
        s2 = Session(data_dir=str(tmp_path))
        assert s2.sql("SELECT v FROM kv WHERE k = 200").rows() == [
            ("NEWER200",)]
        assert s2.sql("SELECT count(*) FROM kv").rows() == [(n,)]
        # compaction materializes the delvecs and resets file count
        s2.store.compact_table("kv")
        m2 = s2.store.read_manifest("kv")
        assert len(m2["rowsets"]) == 1
        assert not any(f.get("delvec") for f in m2["rowsets"][0]["files"])
        s2.cache.invalidate("kv")
        from starrocks_tpu.storage.catalog import StoredTableHandle
        s2.catalog.get_table("kv").invalidate()
        assert s2.sql("SELECT v FROM kv WHERE k = 200").rows() == [
            ("NEWER200",)]
        assert s2.sql("SELECT count(*) FROM kv").rows() == [(n,)]
    finally:
        config.set("compaction_trigger_rowsets", old)


def test_pk_upsert_varchar_and_date_keys(tmp_path):
    """PK matching must be by VALUE across representations: in-memory dict
    codes vs parquet round-trips (regression: code-keyed index corrupted
    VARCHAR/DATE primary keys)."""
    from starrocks_tpu.runtime.session import Session

    s = Session(data_dir=str(tmp_path))
    s.sql("CREATE TABLE sv (k VARCHAR, d DATE, v BIGINT, PRIMARY KEY(k, d))")
    s.sql("INSERT INTO sv VALUES ('a', DATE '2024-01-01', 1),"
          "('b', DATE '2024-01-01', 2)")
    # fresh batch: new dict where 'b' has a different code
    s.sql("INSERT INTO sv VALUES ('b', DATE '2024-01-01', 30)")
    rows = s.sql("SELECT k, v FROM sv ORDER BY k").rows()
    assert rows == [("a", 1), ("b", 30)]
    # restart: index rebuilt from parquet values, must still match
    s2 = Session(data_dir=str(tmp_path))
    s2.sql("INSERT INTO sv VALUES ('a', DATE '2024-01-01', 100),"
           "('c', DATE '2024-02-02', 3)")
    rows = s2.sql("SELECT k, v FROM sv ORDER BY k").rows()
    assert rows == [("a", 100), ("b", 30), ("c", 3)]


def test_datetime_range_partitions(tmp_path):
    from starrocks_tpu.runtime.session import Session

    s = Session(data_dir=str(tmp_path))
    s.sql("CREATE TABLE ev (ts DATETIME, v BIGINT) PARTITION BY RANGE(ts) ("
          " PARTITION h1 VALUES LESS THAN ('2024-01-01 12:00:00'),"
          " PARTITION h2 VALUES LESS THAN (MAXVALUE))")
    s.sql("INSERT INTO ev VALUES ('2024-01-01 08:00:00', 1),"
          "('2024-01-01 18:30:00', 2)")
    parts = s.sql("SHOW PARTITIONS FROM ev")
    assert [p[4] for p in parts] == [1, 1]
    assert "12:00:00" in parts[0][3]
    assert s.sql("SELECT sum(v) FROM ev").rows() == [(3,)]


def test_delete_keeps_partition_metadata(tmp_path):
    from starrocks_tpu.runtime.session import Session

    s = Session(data_dir=str(tmp_path))
    s.sql("CREATE TABLE pd (x BIGINT, v BIGINT) PARTITION BY RANGE(x) ("
          " PARTITION lo VALUES LESS THAN (100),"
          " PARTITION hi VALUES LESS THAN (MAXVALUE))")
    s.sql("INSERT INTO pd VALUES (1, 10), (50, 20), (150, 30)")
    s.sql("DELETE FROM pd WHERE x = 50")
    parts = s.sql("SHOW PARTITIONS FROM pd")
    assert [p[4] for p in parts] == [1, 1]  # rewrite kept partition files
    assert s.sql("SELECT sum(v) FROM pd").rows() == [(40,)]


def test_grace_join_spill():
    """A join whose inputs exceed the forced streaming threshold completes
    via host partition-pair streaming and matches the oracle (VERDICT:
    the Grace-join analog of spiller.h)."""
    import numpy as np
    import pandas as pd

    from starrocks_tpu.column import HostTable
    from starrocks_tpu.runtime.config import config
    from starrocks_tpu.runtime.session import Session
    from starrocks_tpu.storage.catalog import Catalog

    rng = np.random.default_rng(5)
    n, m = 50_000, 20_000
    fact = {"k": rng.integers(0, 30_000, n), "v": rng.integers(0, 100, n)}
    dim = {"k": np.arange(m), "w": rng.integers(0, 10, m)}
    cat = Catalog()
    cat.register("fact", HostTable.from_pydict(
        {k: list(v) for k, v in fact.items()}))
    cat.register("dim", HostTable.from_pydict(
        {k: list(v) for k, v in dim.items()}), unique_keys=[("k",)])
    s = Session(cat)
    old_t = config.get("batch_rows_threshold")
    old_b = config.get("spill_batch_rows")
    config.set("batch_rows_threshold", 8_000)  # force the spill path
    config.set("spill_batch_rows", 8_000)
    try:
        q = ("SELECT w, count(*) c, sum(v) sv FROM fact, dim "
             "WHERE fact.k = dim.k GROUP BY w ORDER BY w")
        r = s.sql(q).rows()
        prof = s.last_profile
        # the partitioned-join executor fired (hybrid by default; grace is
        # the legacy A/B anchor behind SET join_hybrid_strategy='grace')
        assert ("hybrid_partitions" in prof.render()
                or "grace_partitions" in prof.render()), prof.render()[:500]
        # re-execution reuses cached programs + adopted capacities
        assert s.sql(q).rows() == r
        # forced legacy grace path agrees
        config.set("join_hybrid_strategy", "grace")
        try:
            assert s.sql(q).rows() == r
            assert "grace_partitions" in s.last_profile.render()
        finally:
            config.set("join_hybrid_strategy", "auto")
    finally:
        config.set("batch_rows_threshold", old_t)
        config.set("spill_batch_rows", old_b)
    df = pd.DataFrame(fact).merge(pd.DataFrame(dim), on="k")
    exp = df.groupby("w", as_index=False).agg(c=("v", "size"), sv=("v", "sum"))
    assert r == [(int(w), int(c), int(sv))
                 for w, c, sv in exp.itertuples(index=False)]


def test_alter_table_add_drop_column(tmp_path):
    """Linked schema change: ADD COLUMN leaves data files untouched (old
    rows read NULL), DROP is metadata-only; both survive restart."""
    from starrocks_tpu.runtime.session import Session

    s = Session(data_dir=str(tmp_path))
    s.sql("CREATE TABLE t (a BIGINT, b VARCHAR)")
    s.sql("INSERT INTO t VALUES (1, 'x'), (2, 'y')")
    s.sql("ALTER TABLE t ADD COLUMN c DOUBLE")
    assert s.sql("SELECT a, c FROM t ORDER BY a").rows() == [
        (1, None), (2, None)]
    s.sql("INSERT INTO t VALUES (3, 'z', 1.5)")
    assert s.sql("SELECT a, c FROM t ORDER BY a").rows() == [
        (1, None), (2, None), (3, 1.5)]
    assert s.sql("SELECT sum(c) FROM t").rows() == [(1.5,)]
    s.sql("ALTER TABLE t DROP COLUMN b")
    assert [d[0] for d in s.sql("DESCRIBE t")] == ["a", "c"]
    # restart: schema replayed from the manifest
    s2 = Session(data_dir=str(tmp_path))
    assert s2.sql("SELECT a, c FROM t ORDER BY a").rows() == [
        (1, None), (2, None), (3, 1.5)]
    import pytest as _pt

    with _pt.raises(Exception, match="unknown column"):
        s2.sql("SELECT b FROM t")


def test_alter_table_in_memory_and_guards():
    from starrocks_tpu.runtime.session import Session

    s = Session()
    s.sql("CREATE TABLE m (k BIGINT, v BIGINT, PRIMARY KEY(k))")
    s.sql("INSERT INTO m VALUES (1, 10)")
    s.sql("ALTER TABLE m ADD COLUMN note VARCHAR")
    assert s.sql("SELECT k, note FROM m").rows() == [(1, None)]
    import pytest as _pt

    with _pt.raises(Exception, match="cannot be dropped"):
        s.sql("ALTER TABLE m DROP COLUMN k")
    with _pt.raises(Exception, match="NOT NULL"):
        s.sql("ALTER TABLE m ADD COLUMN req BIGINT NOT NULL")


def test_alter_drop_then_readd_reads_null(tmp_path):
    """Re-adding a dropped column name must NOT resurrect the old bytes
    (and type changes must not reinterpret them)."""
    from starrocks_tpu.runtime.session import Session

    s = Session(data_dir=str(tmp_path))
    s.sql("CREATE TABLE t (a BIGINT, b VARCHAR)")
    s.sql("INSERT INTO t VALUES (1, 'xyz'), (2, 'pq')")
    s.sql("ALTER TABLE t DROP COLUMN b")
    s.sql("ALTER TABLE t ADD COLUMN b DOUBLE")
    assert s.sql("SELECT a, b FROM t ORDER BY a").rows() == [
        (1, None), (2, None)]
    s.sql("INSERT INTO t VALUES (3, 4.5)")
    assert s.sql("SELECT sum(b) FROM t").rows() == [(4.5,)]


def test_alter_add_array_column(tmp_path):
    from starrocks_tpu.runtime.session import Session

    s = Session(data_dir=str(tmp_path))
    s.sql("CREATE TABLE v (a BIGINT)")
    s.sql("INSERT INTO v VALUES (1)")
    s.sql("ALTER TABLE v ADD COLUMN arr ARRAY<BIGINT>")
    s.sql("INSERT INTO v VALUES (2, array(7, 8))")
    assert s.sql("SELECT a, arr FROM v ORDER BY a").rows() == [
        (1, None), (2, [7, 8])]
    # in-memory variant
    s2 = Session()
    s2.sql("CREATE TABLE w (a BIGINT)")
    s2.sql("INSERT INTO w VALUES (1)")
    s2.sql("ALTER TABLE w ADD COLUMN arr ARRAY<BIGINT>")
    s2.sql("INSERT INTO w VALUES (2, array(7, 8))")
    assert s2.sql("SELECT a, arr FROM w ORDER BY a").rows() == [
        (1, None), (2, [7, 8])]

def test_image_checkpoint_and_editlog_compaction(tmp_path):
    """Catalog image + journal tail (fe persist/EditLog.java:133 +
    leader/CheckpointController.java:85): a long DDL history auto-compacts
    into an image; restart restores views/MVs/users/grants from
    image + tail without replaying the full history."""
    d = str(tmp_path / "db")
    s = Session(data_dir=d)
    s.sql("create table base (g varchar, v int)")
    s.sql("insert into base values ('a', 1), ('a', 2), ('b', 5)")
    # a 1000-op DDL history: create/drop churn plus surviving metadata
    for i in range(500):
        s.sql(f"create view churn_{i} as select g from base")
        s.sql(f"drop table churn_{i}")
    s.sql("create view keepv as select g, sum(v) sv from base group by g")
    s.sql("create materialized view keepmv as "
          "select g, count(*) c from base group by g")
    s.sql("create user bob identified by 'pw'")
    s.sql("grant select on base to bob")
    # churn crossed the threshold many times: the journal tail stays small
    # and the image exists
    assert os.path.exists(s.store.image_path)
    n_tail = sum(1 for _ in open(s.store.log_path)) \
        if os.path.exists(s.store.log_path) else 0
    assert n_tail <= Session.CHECKPOINT_OPS + 8, n_tail

    # restart: metadata restored from image + tail
    s2 = Session(data_dir=d)
    assert s2.sql("select g, sv from keepv order by g").rows() == [
        ("a", 3), ("b", 5)]
    assert s2.sql("select g, c from keepmv order by g").rows() == [
        ("a", 2), ("b", 1)]
    assert "churn_7" not in s2.catalog.views
    a = s2.auth()
    assert a.verify_plain("bob", "pw")
    assert a.check("bob", "base", "select")
    # a manual checkpoint covers everything: tail empties
    s2.sql("create view lastv as select v from base")
    s2.checkpoint_metadata()
    assert sum(1 for _ in open(s2.store.log_path)) == 0
    s3 = Session(data_dir=d)
    assert "lastv" in s3.catalog.views
    assert s3.sql("select count(*) from keepmv").rows() == [(2,)]


def test_checkpoint_concurrent_log_no_lost_ops(tmp_path):
    """checkpoint() compacts the journal (snapshot tail -> os.replace); a
    concurrent log() append must never land on the replaced inode and
    vanish. The journal lock serializes them — every op logged during a
    storm of checkpoints must survive into image-seq + tail."""
    import threading

    from starrocks_tpu.storage.store import TabletStore

    store = TabletStore(str(tmp_path / "db"))
    store.log({"op": "seed"})
    stop = threading.Event()
    logged = []

    def writer():
        i = 0
        while not stop.is_set():
            logged.append(store.log({"op": "w", "i": i}))
            i += 1

    t = threading.Thread(target=writer)
    t.start()
    try:
        for _ in range(60):
            store.checkpoint({"tables": {}})
    finally:
        stop.set()
        t.join()

    img = store.read_image()
    tail_seqs = {op["seq"] for op in store.replay(after_seq=img["seq"])}
    lost = [s for s in logged if s > img["seq"] and s not in tail_seqs]
    assert lost == [], f"ops lost by checkpoint/log race: {lost}"


def test_ungrouped_filter_sum_matches_numpy(tmp_path):
    """The SSB q1.x shape (ungrouped sum(a*b) under conjunctive integer
    predicates) over a stored table: one `global` masked reduction, held
    to numpy, sum-over-empty -> NULL and a NULL-bearing column included."""
    s = Session(data_dir=str(tmp_path / "dbf"))
    s.sql("create table f (d bigint, disc bigint, qty bigint, "
          "price bigint, nn bigint)")
    i = np.arange(5000)
    d, disc, qty, price = 19940101 + i % 300, i % 11, i % 50, i * 7 % 1000
    rows = ",".join(
        f"({d[k]}, {disc[k]}, {qty[k]}, {price[k]}, "
        f"{'null' if k % 97 == 0 else k})"
        for k in i)
    s.sql(f"insert into f values {rows}")
    m = ((d >= 19940110) & (d <= 19940210) & (disc >= 4) & (disc <= 6)
         & (qty < 25))
    r = s.sql("select sum(price * disc) rev from f "
              "where d >= 19940110 and d <= 19940210 and disc >= 4 "
              "and disc <= 6 and qty < 25")
    assert r.rows() == [(int((price * disc)[m].sum()),)]
    took = [a.infos["segment_sums"] for a in r.profile.children
            if "segment_sums" in a.infos][-1]
    assert {t["formulation"] for t in took.values()} == {"global"}
    assert s.sql("select sum(price) p from f where disc = 3").rows() == [
        (int(price[disc == 3].sum()),)]
    assert s.sql("select sum(price * disc) rev from f "
                 "where qty > 10000").rows() == [(None,)]
    keep = (disc >= 9) & (i % 97 != 0)
    assert s.sql("select sum(nn) z from f where disc >= 9").rows() == [
        (int(i[keep].sum()),)]
