"""The benchmark's reader of SQL operator scopes (`benchmarks/harness/
scopes.py`) and of the compile spans, held to a hand-written trace whose
numbers are known exactly, to a trace recorded on a v5e before any scope
existed, and to the protobuf definition its wire-format walker stands in
for. On the CPU, in seconds: the chip is not needed to read a file."""

import importlib.util
import os
import shutil
import types

import pytest

from benchmarks.harness import cells, scopes, spans, xplane

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(ROOT, "benchmarks", "tests", "data")
Q = "q_0a1b2c3d"


def _serialized(name: str) -> bytes:
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, name)) as f:
        text = "".join(ln for ln in f if not ln.startswith("#"))
    return ProfileData.text_proto_to_serialized_xspace(text)


def _run_over(tmp_path, trace: bytes, records, chips: int = 1):
    """A run as `benchmarks/run.py` hands it to a metric's `compute`, with
    its trace where `run.py` leaves it."""
    trace_dir = tmp_path / "benchmarks" / ".traces" / "cell" / "plugins"
    os.makedirs(trace_dir)
    (trace_dir / "t.xplane.pb").write_bytes(trace)
    cell = types.SimpleNamespace(root=str(tmp_path), name="cell", chips=chips)
    reduced = xplane.reduce(xplane.read(str(trace_dir / "t.xplane.pb")),
                            chips)
    return types.SimpleNamespace(
        cell=cell, trace=reduced,
        window={"epoch_start": 1_700_000_000.0, "records": records})


@pytest.fixture()
def scoped_run(tmp_path):
    # one statement that spans the whole traced slice
    return _run_over(tmp_path, _serialized("scoped.xplane.txt"),
                     [(0, 0, 0.0, 15.5)])


@pytest.mark.parametrize("tf_op,scope", [
    (None, scopes.NO_TF_OP),
    ("", scopes.NO_TF_OP),
    ("inputs[0][0][2]:", scopes.NO_SCOPE),
    ("jit(run)/jit(remainder)/select_n:", "run/" + scopes.NO_SCOPE),
    (f"jit({Q})/sr.sort.0/sr.agg.1/while:", f"{Q}/sr.agg.1"),
    (f"jit({Q})/sr.sort.0/sr.agg.1/segments/limbs/jit(_einsum)/dot_general:",
     f"{Q}/sr.agg.1/limbs"),
    (f"jit({Q})/sr.agg.1/sr.join.2/expand/gather:", f"{Q}/sr.join.2/expand"),
    # a phase of the parent does not reach into the child's scope
    (f"jit({Q})/sr.agg.1/lexsort/sr.join.2/gather:", f"{Q}/sr.join.2"),
    # the primitive `sort` is not the phase `sort`
    (f"jit({Q})/sr.sort.0/sort:", f"{Q}/sr.sort.0"),
    (f"jit({Q})/sr.sort.0/sort/jit(argsort)/sort:", f"{Q}/sr.sort.0/sort"),
])
def test_innermost_scope_and_phase_win(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope
    kind = scope.split("sr.")[1].split(".")[0] if "sr." in scope else None
    assert scopes.kind_of(scope) == kind


def test_nested_self_time_goes_to_each_operations_own_scope(scoped_run, capsys):
    got = scopes.by_scope(scoped_run)
    assert got == pytest.approx({
        f"{Q}/sr.agg.1": 5e-3,           # the while, less what it holds
        f"{Q}/sr.agg.1/limbs": 3e-3,
        f"{Q}/sr.join.2/expand": 2e-3,
        f"{Q}/sr.sort.0/sort": 2e-3,
        scopes.NO_SCOPE: 1e-3,            # the custom call on a parameter
        scopes.NO_TF_OP: 0.5e-3,          # the iota
        f"{Q}/sr.filter.3": 1e-3,         # tf_op kept by reference
        f"{Q}/sr.union.9": 0.5e-3})
    assert sum(got.values()) == pytest.approx(scoped_run.trace["busy_s"])
    out = capsys.readouterr().out
    assert f"scope {Q}/sr.agg.1 self_s=0.005000" in out
    assert f"operation fusion.2 -> {Q}/sr.agg.1/limbs self_s=0.003000" in out
    # an operation outside every scope is printed with what the trace has
    assert ("operation custom-call.5 -> (no sr scope) self_s=0.001000 "
            "at=inputs[0][0][2]:") in out
    scopes.by_scope(scoped_run)  # read once, printed once
    assert capsys.readouterr().out == ""


def test_operator_metrics_add_up_to_the_devices_busy_time(scoped_run):
    value = {name: cells.load_module(ROOT, "layer_metrics", name)
             .compute(scoped_run) for name in (
        "op_scan_ms", "op_agg_ms", "op_join_ms", "op_sort_ms",
        "op_other_share", "device_ms_per_stmt")}
    assert value == pytest.approx({
        "op_scan_ms": 1.0, "op_agg_ms": 8.0, "op_join_ms": 2.0,
        "op_sort_ms": 2.0, "op_other_share": 100 * 2.0 / 15.0,
        "device_ms_per_stmt": 15.0})
    named = sum(value[f"op_{k}_ms"] for k in scopes.KINDS)
    assert named + value["op_other_share"] / 100 * value[
        "device_ms_per_stmt"] == pytest.approx(value["device_ms_per_stmt"])


def test_recorded_trace_from_before_the_scopes_is_all_other(tmp_path):
    with open(os.path.join(DATA, "scan_v5e.xplane.pb"), "rb") as f:
        run = _run_over(tmp_path, f.read(), [])
    got = scopes.by_scope(run)
    assert set(got) == {scopes.NO_TF_OP, scopes.NO_SCOPE,
                        "run/" + scopes.NO_SCOPE}
    assert sum(got.values()) == pytest.approx(run.trace["busy_s"], rel=1e-6)
    # a program without scopes reports no operator metric, not zeros
    assert scopes.other_share(run) is None
    assert scopes.kind_ms(run, "agg") is None


def test_no_device_trace_gives_nothing():
    run = types.SimpleNamespace(trace={})
    assert scopes.by_scope(run) == {}
    assert scopes.other_share(run) is None


def _xplane_pb2():
    """The generated protobuf module, loaded by path: importing the
    tensorflow package that ships it would take the test 7 s."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.origin:
        pytest.skip("no tensorflow, so no xplane_pb2 to compare with")
    path = os.path.join(os.path.dirname(spec.origin), "tsl", "profiler",
                        "protobuf", "xplane_pb2.py")
    if not os.path.exists(path):
        pytest.skip(f"no {path}")
    spec = importlib.util.spec_from_file_location("_xplane_pb2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", ["scoped.xplane.txt", "scan_v5e.xplane.pb",
                                  "dash_v5e_scoped.xplane.pb"])
def test_wire_format_walker_agrees_with_the_protobuf_definition(tmp_path, name):
    pb2 = _xplane_pb2()
    if name.endswith(".txt"):
        raw = _serialized(name)
    else:
        with open(os.path.join(DATA, name), "rb") as f:
            raw = f.read()
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(raw)
    space = pb2.XSpace()
    space.ParseFromString(raw)
    want = {}
    for plane in space.planes:
        m = xplane.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            ops = []
            for e in line.events:
                md = plane.event_metadata[e.metadata_id]
                stats = {names[s.metadata_id]: (
                    s.str_value if s.WhichOneof("value") == "str_value"
                    else names[s.ref_value]) for s in md.stats
                    if s.WhichOneof("value") in ("str_value", "ref_value")}
                start = line.timestamp_ns * 1000 + e.offset_ps
                ops.append((md.name, stats.get("tf_op"), stats.get("source"),
                            start, start + e.duration_ps))
            want[int(m.group(1))] = ops
    assert want and scopes.read_ops(str(path)) == want


def test_recorded_scoped_dash_trace(tmp_path):
    """A slice of PR 24's traced run of `tpch_sf10.dash` on a v5e, scopes in
    the program (cut as benchmarks/tests/test_scopes.py says): the predicate
    runs under `sr.filter`, the sum under `sr.agg`, and the six X64 splits of
    the scan's int64 columns under no scope at all."""
    with open(os.path.join(DATA, "dash_v5e_scoped.xplane.pb"), "rb") as f:
        run = _run_over(tmp_path, f.read(), [])
    got = scopes.by_scope(run)
    assert sum(got.values()) == pytest.approx(run.trace["busy_s"], rel=1e-6)
    assert set(got) == {scopes.NO_SCOPE, scopes.NO_TF_OP,
                        "q_76edc73a/sr.filter.2",
                        "q_76edc73a/sr.agg.1/segments"}
    assert scopes.other_share(run) == pytest.approx(73.474, abs=1e-3)


def test_phases_are_the_programs():
    from starrocks_tpu.ops.common import PHASES

    assert scopes.PHASES == PHASES


def test_compile_spans_of_the_warm_up():
    def run(*statements):
        return types.SimpleNamespace(warm={"statements": {
            i: {"spans": list(st)} for i, st in enumerate(statements)}})

    first = [("compile_first_run", 1.0, 9.0), ("jax_trace", 1.0, 2.0),
             ("jax_lower", 3.0, 0.5), ("xla_compile", 3.5, 4.0),
             ("dispatch", 1.0, 7.0), ("device_wait", 8.0, 1.5)]
    warm = [("dispatch", 20.0, 0.001), ("device_wait", 20.001, 0.01)]
    assert spans.warm_spans_s(run(first, warm), ("jax_trace", "jax_lower")) == 2.5
    assert spans.warm_spans_s(run(first, warm), ("xla_compile",)) == 4.0
    # nothing compiled: a number, not an absence
    assert spans.warm_spans_s(run(warm), ("xla_compile",)) == 0.0
    # a program from before the spans: nothing to read
    assert spans.warm_spans_s(
        run([("compile_and_run", 1.0, 2.0)]), ("xla_compile",)) is None
