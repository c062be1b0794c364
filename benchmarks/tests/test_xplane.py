"""The reduction from a profiler trace to device metrics, on a hand-written
two-chip trace whose numbers are known exactly and on a trace recorded on a
v5e (a traced run of `tpch_sf10.scan`, PR 22)."""

import os

import pytest

from benchmarks.harness import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
EPOCH = 1_700_000_000.0


@pytest.fixture(scope="module")
def two_chips(tmp_path_factory):
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "two_chips.xplane.txt")) as f:
        text = "".join(ln for ln in f if not ln.startswith("#"))
    path = tmp_path_factory.mktemp("trace") / "two_chips.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return xplane.read(str(path))


def test_names_are_shortened_and_async_ops_left_out(two_chips):
    assert sorted(two_chips["devices"]) == [0, 1]
    assert [what for what, _, _ in two_chips["devices"][0]] == [
        ("while.1", "while"), ("all-reduce.3", "all-reduce"),
        ("fusion.12", "fusion")]
    assert xplane.op_name("fusion.7") == ("fusion.7", "fusion")  # a bare name
    assert two_chips["mark"] == (0.0, 1_700_000_000_000_000_000)


def test_busy_self_time_and_collectives(two_chips):
    r = xplane.reduce(two_chips, chips=2)
    assert r["window_s"] == pytest.approx(9e-3)  # first operation to last
    assert r["busy_s"] == pytest.approx((6e-3 + 4.5e-3) / 2)
    # self time: the while holds the all-reduce, so 3 us of its 4 are its own
    assert r["ops_s"] == pytest.approx({
        "while.1": 3e-3 / 2, "all-reduce.3": 1e-3 / 2,
        "fusion.12": (2e-3 + 2e-3) / 2, "all-to-all.4": 2e-3 / 2,
        "all_to_all.56": 0.5e-3 / 2})
    # by opcode: neither the fusion that reads %all-gather.9 nor the reshape
    # that JAX named all_to_all.56 is a collective
    assert r["collective_s"] == pytest.approx((1e-3 + 2e-3) / 2)
    assert xplane.top_ops(r, 2) == [
        ["fusion.12", pytest.approx(2e-3)], ["while.1", pytest.approx(1.5e-3)]]


def test_one_chip_of_two(two_chips):
    r = xplane.reduce(two_chips, chips=1)
    assert r["window_s"] == pytest.approx(8e-3)
    assert r["busy_s"] == pytest.approx(6e-3)
    assert r["collective_s"] == pytest.approx(1e-3)


def test_idle_gaps_go_to_the_span_that_covers_them(two_chips, monkeypatch):
    monkeypatch.setattr(xplane, "SHORT_GAP_S", 0.0)
    r = xplane.reduce(two_chips, chips=2)
    gaps = [(round((a - EPOCH) * 1e3, 3), round((b - EPOCH) * 1e3, 3))
            for a, b in r["idle_gaps"]]
    assert gaps == [(0.0, 1.0), (5.0, 7.0)]  # the busiest chip's
    statements = {1: {"sql": "q", "ms": 2.5, "queue_wait_ms": 0.0, "spans": [
        ("optimize", EPOCH + 5.2e-3, 0.3e-3),
        ("compile_and_run", EPOCH + 5.5e-3, 2e-3),
        ("scan_to_device", EPOCH + 5.5e-3, 0.2e-3)]}}
    idle = dict(xplane.attribute_gaps(r, statements))
    assert idle == pytest.approx({
        "between statements": 1e-3, "compile_and_run": 2e-3},
        abs=1e-6)  # epoch seconds in float64 resolve a quarter of a microsecond
    monkeypatch.setattr(xplane, "SHORT_GAP_S", 5e-3)
    assert list(dict(xplane.attribute_gaps(r, statements))) == [
        "between operations (gaps under 5000 us)"]


def test_no_device_operation_gives_nothing():
    assert xplane.reduce({"devices": {}, "marks": {}}, chips=1) == {}


def test_recorded_v5e_trace():
    """A traced run of `tpch_sf10.scan` on a v5e (PR 22, seed 8): the numbers
    that run printed. The trace ran for 5.0 s from just before the window;
    the span is the 4.946 s from the first operation the profiler kept to the
    end of the last."""
    r = xplane.reduce(xplane.read(os.path.join(DATA, "scan_v5e.xplane.pb")), 1)
    assert r["window_s"] == pytest.approx(4.946264676, abs=1e-6)
    assert r["busy_s"] == pytest.approx(4.742956786, abs=1e-6)
    assert r["collective_s"] == 0.0
    top = xplane.top_ops(r, 4)
    assert top[0] == ["select_reduce_fusion", pytest.approx(1.1077, abs=1e-4)]
    assert {name for name, _ in top[1:]} == {"fusion.30", "fusion.32", "fusion.33"}
    lo, hi = r["slice_epoch"]
    assert hi - lo == pytest.approx(4.9463, abs=1e-3)
    assert 1.79044e9 < lo < 1.79045e9  # 26 September 2026, by the mark
    assert r["idle_gaps"] and all(lo <= a <= b <= hi for a, b in r["idle_gaps"])
