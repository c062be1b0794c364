"""The benchmark rehearsed on the CPU before chip time is spent: every cell's
files load by name, every cell's run passes end to end at SF0.01 (the
four-chip cell on four virtual devices), the entry point refuses anything but
a TPU, and the yardstick's own parts (generator, comparison, statistics,
peaks, bytes, both loops of the load generator) do what they say."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pandas as pd
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
PREPARED = os.path.join(ROOT, "benchmarks", "prepared")

from benchmarks.harness import cells, compare, peaks, stats  # noqa: E402
from benchmarks.harness import bytes as scan_bytes  # noqa: E402


def _python(code_or_args, env_extra=None, cwd=ROOT, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    args = code_or_args if isinstance(code_or_args, list) else ["-c", code_or_args]
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


# --- driven by data: every name resolves to a file -------------------------

@pytest.mark.parametrize("index", range(len(CELLS) + len(os.listdir(PREPARED))))
def test_cell_resolves_by_name(with_prepared, index):
    bench_root, names = with_prepared
    cell = cells.Cell(bench_root, names[index])
    assert cell.variants and all(v["sql"] and "{" not in v["sql"]
                                 for v in cell.variants)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "lat_geomean_ms"}
    for traced in (False, True):
        for entry, reader in cell.readers(traced):
            # the reader's META repeats its BENCHMARK.json entry
            for key, value in reader.META.items():
                assert entry[key] == value, (entry["name"], key)
            assert callable(reader.compute)
    assert cell.per_layer, "every cell reports a per-layer metric"


def test_benchmark_json_keeps_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmarks/run.py"]
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    cited = {c for m in BENCH["end_to_end"] + BENCH["per_layer"]
             for c in m.get("workloads", [])}
    assert cited <= set(CELLS)
    # a per-layer metric is reported only where the metric it moves is
    for m in BENCH["per_layer"]:
        assert set(m.get("workloads", CELLS)) <= set(
            e2e[m["moves"]].get("workloads", CELLS)), m["name"]
    assert all(len(e["why"]) <= 200
               for e in BENCH["configs"] + BENCH["workloads"])
    four =[w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        assert config["reduced"] == c["reduced"]
        assert {"source", "guarantees", "assumed", "expected_rows"} <= set(config)


# --- the run, end to end, on CPU at SF0.01 ----------------------------------

_RUN = """
import json, sys
sys.path.insert(0, {root!r})
from benchmarks import run
run.ROOT = {bench_root!r}
print(json.dumps(run.run_cell({cell!r}, 7, 1.5, {traced}, scale=0.01)))
"""


@pytest.fixture(scope="module")
def with_prepared(tmp_path_factory):
    """A root whose BENCHMARK.json also lists the cells of benchmarks/prepared/
    (entries a later PR adds as they stand), over the same benchmarks/."""
    root = tmp_path_factory.mktemp("prepared")
    bench = json.loads(json.dumps(BENCH))
    for name in sorted(os.listdir(PREPARED)):
        with open(os.path.join(PREPARED, name)) as f:
            entries = json.load(f)
        for key in ("configs", "workloads", "per_layer"):
            bench[key] += entries[key]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    os.symlink(os.path.join(ROOT, "benchmarks"), root / "benchmarks")
    return str(root), [w["name"] for w in bench["workloads"]]


@pytest.mark.parametrize("traced", [False, True], ids=["timed", "traced"])
@pytest.mark.parametrize("index", range(len(CELLS) + len(os.listdir(PREPARED))))
def test_cell_runs_on_cpu(with_prepared, index, traced):
    bench_root, names = with_prepared
    name = names[index]
    cell = cells.Cell(bench_root, name)
    flags = f"--xla_force_host_platform_device_count={cell.chips}"
    out = _python(_RUN.format(root=ROOT, bench_root=bench_root, cell=name,
                              traced=traced), {"XLA_FLAGS": flags})
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["failed"] == 0, out.stdout[-3000:]
    assert result["attempted"] >= len(cell.variants)
    assert result["device"] == {"platform": "cpu", "kind": "cpu",
                                "count": cell.chips, "memory_peak_bytes": None}
    got = set(result["metrics"])
    if traced:
        # what a CPU trace cannot give (no device plane) is left out, not faked
        device_only = {m["name"] for m in cell.per_layer
                       if m["source"] == "device_trace"}
        assert got == {m["name"] for m in cell.per_layer} - device_only
        assert result["metrics"]["window_compiles"]["value"] == 0
        assert "breakdown" not in result
    else:
        # peak_hbm_gb needs memory statistics, which the CPU backend lacks,
        # and lat_p95_ms a window of at least 200 statements
        absent = {"peak_hbm_gb"} | (
            {"lat_p95_ms"} if result["attempted"] < 200 else set())
        assert got == {m["name"] for m in cell.end_to_end} - absent
        assert all(m["value"] > 0 for m in result["metrics"].values())
    assert "template tpch." in out.stdout and "client_cpu_share=" in out.stdout


def test_main_refuses_a_cpu():
    out = _python(["benchmarks/run.py", "--workload", CELLS[0], "--seed", "1",
                   "--seconds", "1", "--trace", "0"], timeout=120)
    assert out.returncode != 0
    assert "'cpu'" in out.stderr
    assert "{" not in out.stdout  # refused before any work, no result line


def test_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns(".traces", "__pycache__"))
    out = _python(["benchmarks/run.py", "--workload", CELLS[0], "--seed", "1",
                   "--seconds", "1", "--trace", "0"], cwd=tmp_path, timeout=120)
    assert out.returncode != 0 and "{" not in out.stdout


# --- the generator and its guard ----------------------------------------------

@pytest.mark.parametrize("sf,seed", [(0.002, 3), (0.05, 42)])
def test_generator_copy_equals_the_programs(sf, seed):
    from starrocks_tpu.storage.datagen.tpch import gen_tpch

    ours = cells.load_module(ROOT, "datagen", "tpch").generate(sf, seed)
    theirs = gen_tpch(sf, seed)
    assert list(ours) == list(theirs)
    for name, want in theirs.items():
        got = ours[name]
        assert ([(f.name, f.type, f.nullable) for f in got.schema.fields]
                == [(f.name, f.type, f.nullable) for f in want.schema.fields])
        assert not got.valids and not want.valids
        for fg, fw in zip(got.schema.fields, want.schema.fields):
            a, b = got.arrays[fg.name], want.arrays[fw.name]
            assert a.dtype == b.dtype and np.array_equal(a, b), (name, fg.name)
            if fw.dict is not None:
                assert list(fg.dict.values) == list(fw.dict.values)


def test_generator_guard():
    from benchmarks.harness import sut

    config = {"generator": "tpch", "scale_factor": 0.01, "data_seed": 7,
              "expected_rows": {"orders": 15000, "lineitem": 60117},
              "expected_sha256": {}}
    tables, _ = sut.make_tables(config, ROOT, 0.01)
    assert tables["lineitem"].num_rows == 60117
    for wrong in ({"expected_rows": {"lineitem": 60118}}, {"data_seed": 8},
                  {"expected_sha256": {"orders.o_custkey": "0" * 64}}):
        with pytest.raises(sut.GeneratorGuard):
            sut.make_tables({**config, **wrong}, ROOT, 0.01)
    # the rehearsal's scale is not the configuration's: nothing to hold it to
    sut.make_tables({**config, "expected_rows": {"orders": 1}}, ROOT, 0.02)


def test_reference_is_computed_once_per_checkout(tmp_path, monkeypatch):
    from benchmarks.harness import reference

    cell = cells.Cell(ROOT, "tpch_sf10.dash")
    monkeypatch.setattr(cell, "root", str(tmp_path))  # the cache's home
    os.makedirs(tmp_path / "benchmarks")
    for d in ("datagen", "harness"):
        shutil.copytree(os.path.join(ROOT, "benchmarks", d),
                        tmp_path / "benchmarks" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    tables = cells.load_module(ROOT, "datagen", "tpch").generate(0.01, 42)
    first = reference.Reference(cell, tables, 0.01)
    rows = [first.expected(v) for v in cell.variants]
    assert len({float(r.revenue.iloc[0]) for r in rows}) == len(cell.variants)
    again = reference.Reference(cell, tables, 0.01)
    monkeypatch.setattr(again, "frames", lambda: pytest.fail("not cached"))
    assert all(a.equals(b) for a, b in
               zip(rows, [again.expected(v) for v in cell.variants]))
    other_scale = reference.Reference(cell, tables, 0.02)
    assert other_scale._key(cell.variants[0]) != first._key(cell.variants[0])


# --- comparison, statistics, peaks, bytes -------------------------------------

def test_comparison_finds_what_differs():
    exp = pd.DataFrame({"k": [2, 1], "v": [10.0, 20.0],
                        "d": pd.to_datetime(["1995-03-15", "1995-03-16"])})
    rows = [("2", "10.000001", "1995-03-15"), ("1", "20.0", "1995-03-16")]
    assert compare.first_mismatch(rows, exp, None) is None
    assert compare.first_mismatch(rows[::-1], exp, 0) is None  # matched by key
    assert "row 0" in compare.first_mismatch(rows[::-1], exp, None)
    assert "column 1" in compare.first_mismatch(
        [("2", "10.0001", "1995-03-15"), rows[1]], exp, None)  # 1e-5 off
    assert "column 2" in compare.first_mismatch(
        [("2", "10.0", "1995-03-14"), rows[1]], exp, None)
    assert "rows" in compare.first_mismatch(rows[:1], exp, None)
    assert "column 1" in compare.first_mismatch(
        [("2", None, "1995-03-15"), rows[1]], exp, None)


def test_statistics():
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert stats.median(xs) == 3.0 == np.median(xs)
    assert stats.percentile(xs, 0.95) == pytest.approx(np.percentile(xs, 95))
    assert stats.percentile([7.0], 0.95) == 7.0
    assert stats.geomean([550.0, 18.0]) == pytest.approx((550.0 * 18.0) ** 0.5)
    with pytest.raises(ValueError):
        stats.median([])


def test_peaks_and_bytes():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9")
    tables = cells.load_module(ROOT, "datagen", "tpch").generate(0.01, 7)
    q6 = cells.load_module(ROOT, "oracles", "tpch", "q6").COLUMNS
    # l_quantity, l_extendedprice, l_discount as int64, l_shipdate as int32
    assert scan_bytes.scan_bytes(tables, q6) == 60117 * (8 + 8 + 8 + 4)


# --- the load generator's loops, against a live server -------------------------

@pytest.fixture(scope="module")
def door():
    from starrocks_tpu.column import HostTable
    from starrocks_tpu.runtime.mysql_service import MySQLServer
    from starrocks_tpu.runtime.session import Session
    from starrocks_tpu.storage.catalog import Catalog

    cat = Catalog()
    cat.register("t", HostTable.from_pydict({"v": [1, 2, 3]}))
    srv = MySQLServer(Session(cat), port=0).start()
    yield srv.port
    srv.shutdown()


VARIANTS = [{"name": "sum", "sql": "select sum(v) from t"},
            {"name": "cnt", "sql": "select count(*) from t"},
            {"name": "bad", "sql": "select * from no_such_table"}]


def test_closed_loop_counts_failures_and_keeps_answers(door):
    from benchmarks.harness.client import Fleet

    fleet = Fleet(door, 2, VARIANTS)
    try:
        warm = fleet.warm()
        assert len(warm["errors"]) == 2 and warm["last_rows"][:2] == [
            [("6",)], [("3",)]]
        w = fleet.window(0.5, "closed", "cycle", seed=1)
        ok, bad = len(w["records"]), len(w["errors"])
        assert ok > 0 and bad > 0 and abs(ok - 2 * bad) <= 4  # one in three fails
        assert all(len(d) == 1 for d in w["digests"][:2]) and not w["digests"][2]
        assert {c for _, c, _, _ in w["records"]} == {0, 1}
        assert all(start < 0.5 for _, _, start, _ in w["records"])
        # a statement longer than the window: min_cycles keeps the loop going
        w = fleet.window(0.0, "closed", "cycle", seed=1, min_cycles=2)
        assert len(w["records"]) + len(w["errors"]) == 2 * 2 * len(VARIANTS)
    finally:
        fleet.close()


def test_zipf_order_comes_from_the_seed(door):
    from benchmarks.harness.client import Fleet

    fleet = Fleet(door, 1, VARIANTS[:2])
    try:
        fleet.warm()  # compiled before anything is counted

        def picks(seed):
            w = fleet.window(1.5, "closed", "zipf", seed=seed)
            return [vi for vi, _, _, _ in w["records"]]

        a, b, c = picks(5), picks(5), picks(6)
        n = min(len(a), len(b), len(c))
        assert n >= 8 and a[:n] == b[:n] and a[:n] != c[:n]
        assert a.count(0) > a.count(1)  # rank 0 has twice rank 1's weight
    finally:
        fleet.close()


def test_open_loop_offers_its_rate_and_times_from_due(door):
    from benchmarks.harness.client import Fleet

    fleet = Fleet(door, 2, VARIANTS[:2])
    try:
        fleet.warm()
        # 20 a second is under what two connections sustain here (~45 ms each)
        w = fleet.window(1.5, "open", "cycle", seed=3, rate_per_s=20.0)
        assert 15 <= len(w["records"]) <= 50 and not w["errors"]
        starts = [start for _, _, start, _ in w["records"]]
        assert 0.0 < min(starts) and max(starts) < 1.5
        assert w["generator_late_ms"] < 50
    finally:
        fleet.close()
