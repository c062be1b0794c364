"""The benchmark's two readers of the exchange layer
(`benchmarks/layer_metrics/op_exchange_ms.py`, `exchange_mb_per_stmt.py`),
held to a hand-written trace of two chips whose numbers are known exactly, to
traces and counters of a program from before the exchange scope (where they
report nothing and do not raise), and to their entries in BENCHMARK.json."""

import json
import os
import types

import pytest

from benchmarks.harness import cells, scopes

# (`_serialized` joins its name to benchmarks/tests/data: a whole path stays)
from test_bench_scopes import _run_over, _serialized

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "tests", "data")
BENCH_DATA = os.path.join(ROOT, "benchmarks", "tests", "data")
Q = "q_0a1b2c3d_f1"


def _reader(name: str):
    return cells.load_module(ROOT, "layer_metrics", name)


@pytest.mark.parametrize("tf_op,scope", [
    (None, None), ("", None), ("inputs[0][0][2]:", None),
    (f"jit({Q})/shard_map/sr.join.3/probe/gather:", None),
    # a primitive called like the scope is not the scope
    (f"jit({Q})/shard_map/sr.join.3/exchange:", None),
    (f"jit({Q})/shard_map/sr.join.3/exchange/pack/jit(argsort)/sort:",
     f"{Q}/sr.join.3/exchange/pack"),
    (f"jit({Q})/shard_map/sr.sort.0/sr.agg.2/exchange/collective/all_gather:",
     f"{Q}/sr.agg.2/exchange/collective"),
    (f"jit({Q})/shard_map/sr.join.3/rf/exchange/while:",
     f"{Q}/sr.join.3/exchange/other"),
])
def test_exchange_scope_of_an_operation(tf_op, scope):
    assert _reader("op_exchange_ms").exchange_scope(tf_op) == scope


def test_exchange_time_is_split_in_two_and_averaged_over_the_chips(
        tmp_path, capsys):
    # two statements lie whole in the 14 ms slice
    trace = _serialized(os.path.join(HERE, "exchange.xplane.txt"))
    run = _run_over(tmp_path, trace, [(0, 0, 0.0, 10.0), (1, 0, 0.010, 4.0)],
                    chips=2)
    assert _reader("op_exchange_ms").compute(run) == pytest.approx(8.5 / 2)
    out = capsys.readouterr().out
    assert f"scope {Q}/sr.join.3/exchange/pack self_s=0.005000" in out
    assert f"scope {Q}/sr.join.3/exchange/collective self_s=0.001500" in out
    assert "op_exchange_ms exchange/pack ms_per_stmt=2.500" in out
    assert "op_exchange_ms exchange/collective ms_per_stmt=0.750" in out
    assert "op_exchange_ms exchange/other ms_per_stmt=1.000" in out
    # the harness's own reader keeps the operations under their operator,
    # so op_join_ms reads as before: all a chip was busy (13 ms and 14: chip
    # 0 waits a millisecond after its all-to-all), over the two statements
    assert scopes.kind_ms(run, "join") == pytest.approx(13.5 / 2)
    assert _reader("collective_share").compute(run) == pytest.approx(
        100 * 1.5 / 13.5)
    # one chip of the two: that chip's numbers alone
    one = _run_over(tmp_path / "one", trace, [(0, 0, 0.0, 14.0)])
    assert _reader("op_exchange_ms").compute(one) == pytest.approx(8.0)


@pytest.mark.parametrize("name", ["scoped.xplane.txt",
                                  "dash_v5e_scoped.xplane.pb"])
def test_a_program_without_the_scope_reports_nothing(tmp_path, name):
    path = os.path.join(BENCH_DATA, name)
    if name.endswith(".txt"):
        raw = _serialized(path)
    else:
        with open(path, "rb") as f:
            raw = f.read()
    run = _run_over(tmp_path, raw, [(0, 0, 0.0, 15.5)])
    assert run.trace and _reader("op_exchange_ms").compute(run) is None
    assert _reader("op_exchange_ms").compute(
        types.SimpleNamespace(trace={})) is None


def test_exchange_megabytes_per_statement_is_the_counters_delta():
    reader = _reader("exchange_mb_per_stmt")
    window = {"records": [(0, 0, 0.0, 1.0)] * 4}
    run = types.SimpleNamespace(window=window, counters={
        "sr_tpu_exchange_bytes_total": 2 * 21_983_232 + 2 * 310_272})
    assert reader.compute(run) == pytest.approx(11.146752)
    # a one-chip statement moves no byte: a number, not an absence
    run.counters = {"sr_tpu_exchange_bytes_total": 0}
    assert reader.compute(run) == 0.0
    # a program from before the counter, and an empty window: nothing
    run.counters = {"sr_tpu_queries_total": 4}
    assert reader.compute(run) is None
    assert reader.compute(types.SimpleNamespace(
        window={"records": []},
        counters={"sr_tpu_exchange_bytes_total": 7})) is None


def test_the_cell_and_its_exchange_metrics_are_listed():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = "tpch_sf10_x4.join"
    (listed,) = [w for w in bench["workloads"] if w["name"] == name]
    assert listed == {
        "name": name, "config": "tpch_sf10_x4", "traffic": "join_scan_cycle",
        "chips": 4, "why": listed["why"]}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 2)
    cell = cells.Cell(ROOT, name)
    assert cell.config["dist_shards"] == 4 and cell.config["chips"] == 4
    assert cell.config["reduced"] == ["scale_factor"]
    assert [t["name"] for t in cell.templates] == ["tpch.q3", "tpch.q1"]
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "lat_geomean_ms", "peak_hbm_gb"}
    exchange = {m["name"]: m for m in cell.per_layer
                if m["layer"] == "exchange"}
    assert set(exchange) == {"collective_share", "op_exchange_ms",
                             "exchange_mb_per_stmt"}
    for entry, reader in cell.readers(True):
        for key, value in reader.META.items():
            assert entry[key] == value, (entry["name"], key)
    for m in exchange.values():
        assert m["workloads"] == [name] and m["moves"] == "lat_geomean_ms"
    # the one-chip cells report none of them
    assert not [m for m in cells.Cell(ROOT, "tpch_sf10.join").per_layer
                if m["layer"] == "exchange"]
