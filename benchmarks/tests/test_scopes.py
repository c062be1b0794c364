"""The reduction from a trace to device time by SQL operator scope
(`harness/scopes.py`), on a slice of a trace recorded on a v5e with the scopes
in the program: the traced run of `tpch_sf10.dash` of PR 24 (seed 2100000011),
cut to its first 1,200 device operations (100 statements, 1.15 s) and to the
stats a reader uses, so that it stays small; the host plane keeps the
harness's mark and the program's `sr:<name>` annotations. `tests/
test_bench_scopes.py` (tier-1) holds the reader to a hand-written trace."""

import os
import types

import pytest

from benchmarks.harness import scopes, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PATH = os.path.join(DATA, "dash_v5e_scoped.xplane.pb")
Q6 = "q_76edc73a"


def test_recorded_dash_slice_by_scope():
    by_scope, by_op = scopes.attribute(scopes.read_ops(PATH), 1)
    assert by_scope == pytest.approx({
        scopes.NO_SCOPE: 0.846709547,          # six X64Split custom calls
        f"{Q6}/sr.filter.2": 0.166667577,      # the predicate, `fusion.2`
        f"{Q6}/sr.agg.1/segments": 0.139087424,
        scopes.NO_TF_OP: 0.000210808}, abs=1e-9)
    r = xplane.reduce(xplane.read(PATH), 1)
    assert sum(by_scope.values()) == pytest.approx(r["busy_s"], rel=1e-6)
    assert r["busy_s"] == pytest.approx(1.152674805, abs=1e-6)
    # the operations PR 22 could name only `custom-call.N`: each splits one
    # int64 column of the scan (`inputs[0][0][k]`) into u32 halves, outside
    # every operator scope
    splits = {(name, at): s for (name, scope, at), s in by_op.items()
              if scope == scopes.NO_SCOPE}
    assert sorted(splits) == [
        ("custom-call", "inputs[0][0][2]:"),
        ("custom-call.1", "inputs[0][0][2]:"),
        ("custom-call.2", "inputs[0][0][0]:"),
        ("custom-call.3", "inputs[0][0][0]:"),
        ("custom-call.4", "inputs[0][0][1]:"),
        ("custom-call.5", "inputs[0][0][1]:")]
    assert all(0.139 < s < 0.144 for s in splits.values())


def test_recorded_dash_slice_as_metrics(tmp_path):
    trace_dir = tmp_path / "benchmarks" / ".traces" / "tpch_sf10.dash" / "x"
    os.makedirs(trace_dir)
    os.symlink(PATH, trace_dir / "t.xplane.pb")
    r = xplane.reduce(xplane.read(PATH), 1)
    lo, hi = r["slice_epoch"]
    # a hundred statements, back to back, fill the slice
    step = (hi - lo) / 100
    run = types.SimpleNamespace(
        cell=types.SimpleNamespace(root=str(tmp_path), name="tpch_sf10.dash",
                                   chips=1),
        trace=r, window={"epoch_start": lo, "records": [
            (0, 0, i * step, step * 1e3) for i in range(100)]})
    assert scopes.kind_ms(run, "scan") == pytest.approx(1.66667577, rel=1e-4)
    assert scopes.kind_ms(run, "agg") == pytest.approx(1.39087424, rel=1e-4)
    assert scopes.kind_ms(run, "join") == 0.0
    assert scopes.other_share(run) == pytest.approx(73.474, abs=1e-3)


def test_program_timers_sit_on_the_traces_host_plane():
    from jax.profiler import ProfileData

    names = {}
    for plane in ProfileData.from_file(PATH).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    names[e.name] = names.get(e.name, 0) + 1
    assert {"sr:optimize", "sr:compile_and_run", "sr:scan_to_device",
            "sr:dispatch", "sr:device_wait", "sr:fetch_results"} <= set(names)
    assert names["sr:dispatch"] == names["sr:device_wait"] >= 100
