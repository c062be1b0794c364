"""The flat-table cell `ssb_flat_sf100_share.cycle13x10` rehearsed on the CPU
before chip time is spent: its run passes end to end through `run_cell` on a
share of 75,000 rows, its generator makes the table the configuration states
(38 columns at the published types, SSB's dependencies, the key's order, the
same from the same seed), and its three readers (`op_compact_ms`,
`compact_fill`, `flat_scan_roofline`) give on a hand-written trace and on
hand-made counters the numbers worked out by hand."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmarks.harness import cells, flat_bytes, xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NAME = "ssb_flat_sf100_share.cycle13x10"
CELL = cells.Cell(ROOT, NAME)
NEW = ("op_compact_ms", "compact_fill", "flat_scan_roofline")

_RUN = """
import json, sys
sys.path.insert(0, {root!r})
from benchmarks import run
print(json.dumps(run.run_cell({cell!r}, 2147483659, 1.0, {traced}, scale=0.1)))
"""


def test_the_cell_is_as_the_issue_names_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == NAME)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "ssb_flat_sf100_share", "ssb_flat_cycle13x10", 1)
    mine = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in mine] == list(NEW)
    assert all(NAME in m["workloads"] and m["moves"] == "lat_geomean_ms"
               for m in mine)
    t = CELL.traffic
    assert (t["loop"], t["clients"], t["order"], t["trace_seconds"]) == (
        "closed", 1, "cycle", 8.0) and 10 <= t["min_cycles"] <= 16
    assert len(CELL.variants) == 13 and not any(v["params"] for v in CELL.variants)
    for entry, reader in CELL.readers(True):
        for key, value in reader.META.items():
            assert entry[key] == value, (entry["name"], key)
    config = CELL.config
    assert config["reduced"] == ["rows_held"] and "rows_held" in config["reduced_why"]
    assert config["scale_factor"] == 100 and config["dist_shards"] is None
    assert 74_000_000 < config["expected_rows"]["lineorder_flat"] < 76_000_000
    assert all(len(v) == 64 for v in config["expected_sha256"].values())


@pytest.mark.parametrize("traced", [False, True], ids=["timed", "traced"])
def test_cell_runs_on_cpu(traced):
    out = subprocess.run(
        [sys.executable, "-c", _RUN.format(root=ROOT, cell=NAME,
                                          traced=traced)],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["failed"] == 0, out.stdout[-3000:]
    # `min_cycles` whole cycles whatever the window takes
    assert result["attempted"] >= 13 * CELL.traffic["min_cycles"]
    for q in ("q1.1", "q3.4", "q4.3"):
        assert f"template ssb_flat.{q} n=" in out.stdout
    got = result["metrics"]
    if traced:
        # a CPU trace has no device plane: the two device_trace metrics are
        # left out, the counter's is there
        assert "op_compact_ms" not in got and "flat_scan_roofline" not in got
        assert 0 < got["compact_fill"]["value"] <= 100
        assert got["window_compiles"]["value"] == 0
    else:
        assert set(got) == {"setup_s", "lat_geomean_ms"}


# --- the generator -------------------------------------------------------------

@pytest.fixture(scope="module")
def share():
    return cells.load_module(ROOT, "datagen", "ssb_flat").generate(0.1, 42)[
        "lineorder_flat"]


def test_generator_makes_the_published_table(share):
    kinds = {f.name: f.type.kind.name for f in share.schema.fields}
    assert len(kinds) == 38 and list(kinds)[:3] == [
        "LO_ORDERDATE", "LO_ORDERKEY", "LO_LINENUMBER"]
    assert {k: kinds[k] for k in (
        "LO_ORDERDATE", "LO_ORDERKEY", "LO_QUANTITY", "LO_EXTENDEDPRICE",
        "LO_DISCOUNT", "LO_REVENUE", "LO_SUPPLYCOST", "C_CITY", "P_BRAND")} == {
        "LO_ORDERDATE": "DATE", "LO_ORDERKEY": "INT", "LO_QUANTITY": "TINYINT",
        "LO_EXTENDEDPRICE": "INT", "LO_DISCOUNT": "TINYINT",
        "LO_REVENUE": "INT", "LO_SUPPLYCOST": "INT", "C_CITY": "VARCHAR",
        "P_BRAND": "VARCHAR"}
    a = share.arrays
    assert all(a[f.name].dtype == f.type.np_dtype for f in share.schema.fields)
    assert not share.valids and 74_000 < share.num_rows < 76_000
    sizes = {f.name: len(f.dict) for f in share.schema.fields if f.dict is not None}
    assert (sizes["C_CITY"], sizes["S_NATION"], sizes["C_REGION"], sizes["P_MFGR"],
            sizes["P_CATEGORY"], sizes["P_BRAND"]) == (250, 25, 5, 5, 25, 1000)
    # SSB's dependencies
    ext, disc = a["LO_EXTENDEDPRICE"].astype(np.int64), a["LO_DISCOUNT"]
    assert np.array_equal(a["LO_REVENUE"], ext * (100 - disc) // 100)
    assert 1 <= a["LO_QUANTITY"].min() and a["LO_QUANTITY"].max() == 50
    assert (disc.min(), disc.max()) == (0, 10)
    day = np.datetime64("1970-01-01")
    assert day + a["LO_ORDERDATE"].min() == np.datetime64("1992-01-01")
    assert day + a["LO_ORDERDATE"].max() == np.datetime64("1998-08-02")
    text = {f.name: f.dict.values for f in share.schema.fields if f.dict is not None}
    for side in "CS":
        city = text[f"{side}_CITY"][a[f"{side}_CITY"]]
        nation = text[f"{side}_NATION"][a[f"{side}_NATION"]]
        assert all(c[:9].rstrip() == n[:9].rstrip() for c, n in zip(city[:2000], nation))
        region_of = {}
        for n, r in zip(a[f"{side}_NATION"], a[f"{side}_REGION"]):
            assert region_of.setdefault(n, r) == r
    brand = text["P_BRAND"][a["P_BRAND"]][:2000]
    category = text["P_CATEGORY"][a["P_CATEGORY"]][:2000]
    mfgr = text["P_MFGR"][a["P_MFGR"]][:2000]
    assert all(b.startswith(c) and c.startswith(m) and 1 <= int(b[len(c):]) <= 40
               for b, c, m in zip(brand, category, mfgr))
    # rows lie in the order of DUPLICATE KEY(LO_ORDERDATE, LO_ORDERKEY)
    key = (a["LO_ORDERDATE"].astype(np.int64) << 40) | (
        a["LO_ORDERKEY"].astype(np.int64) << 8) | a["LO_LINENUMBER"]
    assert np.all(np.diff(key) > 0)


def test_generator_follows_its_seed_and_nothing_else(share):
    gen = cells.load_module(ROOT, "datagen", "ssb_flat")
    again = gen.generate(0.1, 42)["lineorder_flat"]
    other = gen.generate(0.1, 43)["lineorder_flat"]
    for name in ("LO_ORDERKEY", "LO_REVENUE", "P_BRAND", "S_CITY"):
        assert np.array_equal(share.arrays[name], again.arrays[name])
    assert not np.array_equal(share.arrays["LO_REVENUE"][:1000],
                              other.arrays["LO_REVENUE"][:1000])


# --- the three readers, by hand ------------------------------------------------

def test_flat_scan_bytes_count_the_declared_widths(share):
    fields = {f.name: f for f in share.schema.fields}
    assert {n: flat_bytes.column_width(fields[n]) for n in (
        "LO_ORDERDATE", "LO_QUANTITY", "LO_REVENUE", "C_REGION", "C_CITY",
        "P_CATEGORY", "P_BRAND")} == {
        "LO_ORDERDATE": 4, "LO_QUANTITY": 1, "LO_REVENUE": 4, "C_REGION": 1,
        "C_CITY": 1, "P_CATEGORY": 1, "P_BRAND": 2}
    per_row = {v["template"].split(".", 1)[1]: flat_bytes.flat_scan_bytes(
        {"lineorder_flat": share}, v["oracle"].COLUMNS) // share.num_rows
        for v in CELL.variants}
    assert per_row == {"q1.1": 10, "q1.2": 10, "q1.3": 10, "q2.1": 12,
                       "q2.2": 11, "q2.3": 11, "q3.1": 12, "q3.2": 12,
                       "q3.3": 10, "q3.4": 10, "q4.1": 16, "q4.2": 17,
                       "q4.3": 17}
    # not what the host table holds: a code is four bytes there
    assert share.arrays["P_BRAND"].itemsize == 4


def _traced_run(tmp_path, share):
    """A run as `run.py` hands it to `compute`: the hand-written trace where
    `run.py` leaves it, and a window whose one statement, Q2.1, spans it."""
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "flat_compact.xplane.txt")) as f:
        text = "".join(ln for ln in f if not ln.startswith("#"))
    trace_dir = tmp_path / "benchmarks" / ".traces" / NAME / "plugins"
    os.makedirs(trace_dir)
    path = trace_dir / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    cell = types.SimpleNamespace(root=str(tmp_path), name=NAME, chips=1,
                                 variants=CELL.variants)
    return types.SimpleNamespace(
        cell=cell, trace=xplane.reduce(xplane.read(str(path)), 1),
        tables={"lineorder_flat": share}, device={"kind": "TPU v5 lite"},
        window={"epoch_start": 1_700_000_000.0, "records": [(3, 0, 0.0, 10.0)]})


def test_readers_on_a_hand_written_trace(tmp_path, share):
    run = _traced_run(tmp_path, share)
    assert run.trace["busy_s"] == pytest.approx(9.5e-3)
    value = {name: cells.load_module(ROOT, "layer_metrics", name).compute(run)
             for name in ("op_compact_ms", "op_agg_ms", "op_scan_ms",
                          "op_sort_ms", "device_ms_per_stmt",
                          "flat_scan_roofline")}
    # the index and both gathers; a part of the aggregate's 7 ms
    assert value["op_compact_ms"] == pytest.approx(4.5)
    assert (value["op_agg_ms"], value["op_scan_ms"], value["op_sort_ms"],
            value["device_ms_per_stmt"]) == pytest.approx((7.0, 2.0, 0.5, 9.5))
    # Q2.1 reads 12 bytes a row at the declared widths
    assert value["flat_scan_roofline"] == pytest.approx(
        100 * share.num_rows * 12 / 819e9 / 9.5e-3)


def test_readers_report_nothing_where_there_is_nothing_to_read():
    load = lambda name: cells.load_module(ROOT, "layer_metrics", name)  # noqa: E731
    untraced = types.SimpleNamespace(trace={}, counters={}, window={"records": []})
    assert all(load(name).compute(untraced) is None for name in NEW)
    fill = load("compact_fill")
    run = types.SimpleNamespace(counters={
        "sr_tpu_compact_rows_live_total": 300, "sr_tpu_compact_slots_out_total": 1024})
    assert fill.compute(run) == pytest.approx(100 * 300 / 1024)
    # a window whose statements compact nothing
    run.counters = {"sr_tpu_compact_rows_live_total": 0,
                    "sr_tpu_compact_slots_out_total": 0}
    assert fill.compute(run) is None
