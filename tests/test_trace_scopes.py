"""Names on what a statement does, for whoever reads a trace: SQL operator
scopes (`sr.<kind>.<n>`, phases inside joins and aggregates) in the HLO
metadata of the compiled program, the host spans that split a statement's
compile (`jax_trace`, `jax_lower`, `xla_compile`) and its run (`dispatch`,
`device_wait`), the profile's timers as `sr:<name>` events of a
`jax.profiler` trace, and the program's name. None of it may move what
keys on plan ordinals: the goldens are the parent commit's."""

import json
import os
import re
import threading

import jax
import pytest

from starrocks_tpu.column import HostTable
from starrocks_tpu.ops.common import INDEX_METHOD, PHASES
from starrocks_tpu.runtime.config import config
from starrocks_tpu.runtime.session import Session
from starrocks_tpu.storage.catalog import Catalog, tpch_catalog

from lowering import SCOPED, lowered_text, scope_paths
from tpch_queries import QUERIES

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "trace_scopes_parent.json")) as _f:
    PARENT = json.load(_f)  # taken at 9b96640, before any scope existed

# scopes each statement must lower with at SF0.01 (Q3's three-way join
# fuses into one multiway probe under its top join)
EXPECTED = {
    1: {"sr.sort.0/sort", "sr.sort.0/sr.project.1/sr.agg.2/segments",
        "sr.sort.0/sr.project.1/sr.agg.2/segments/limbs",
        "sr.sort.0/sr.project.1/sr.agg.2/sr.filter.3"},
    3: {"sr.sort.0/sort", "sr.sort.0/sr.project.1/sr.agg.2/lexsort",
        "sr.sort.0/sr.project.1/sr.agg.2/sr.join.3/build",
        "sr.sort.0/sr.project.1/sr.agg.2/sr.join.3/probe",
        "sr.sort.0/sr.project.1/sr.agg.2/sr.join.3/compact",
        "sr.sort.0/sr.project.1/sr.agg.2/sr.join.3/payload",
        "sr.sort.0/sr.project.1/sr.agg.2/sr.join.3/sr.filter.4"},
    6: {"sr.project.0/sr.agg.1/segments",
        "sr.project.0/sr.agg.1/sr.filter.2"},
}


@pytest.fixture(scope="module")
def tpch():
    return tpch_catalog(0.01)


@pytest.fixture(scope="module")
def ran(tpch):
    """q -> (session, the first send's result, its EXPLAIN ANALYZE text),
    each statement on a session of its own."""
    out = {}
    for q in EXPECTED:
        s = Session(tpch)
        text = s.sql("explain analyze " + QUERIES[q])
        out[q] = (s, s.sql(QUERIES[q]), text)
    return out


@pytest.fixture(scope="module")
def lowered(ran):
    return {q: lowered_text(s, r) for q, (s, r, _) in ran.items()}


@pytest.mark.parametrize("q", sorted(EXPECTED))
def test_statement_lowers_with_operator_scopes(lowered, q):
    paths = scope_paths(lowered[q], PHASES)
    assert EXPECTED[q] <= paths, sorted(paths)
    # every scope an operation sits in hangs under the root's
    root = sorted(EXPECTED[q])[0].split("/")[0]
    assert all(p.startswith(root) for p in paths)


def test_q3_compacts_by_index_and_gather_without_a_scatter(lowered):
    """`compact` stays the phase (EXPECTED[3]); below it an `index` and a
    `gather` scope, and no scatter: on a v5e a scatter costs ~100 ns per
    input row and int64 column, which was 32 of Q3's 35 s at SF10."""
    stacks = [p.split("/") for p in SCOPED.findall(lowered[3])
              if "/compact/" in p]
    below = {s[s.index("compact") + 1] for s in stacks}
    assert {"index", "gather"} <= below, sorted(below)
    assert not [s for s in stacks if s[-1].startswith("scatter")]
    assert any(s[-1] == "gather" and "gather" in s[:-1] for s in stacks)


def _last_attempt(result):
    return [a for a in result.profile.children
            if "capacities" in a.infos][-1]


def test_q1_sums_every_integer_column_in_one_batch(ran, lowered):
    """Q1 has eight aggregates that ask for sixteen integer sums: four sums
    with their nonempty counts, three averages with theirs, count(*) and the
    group count. Under `sr.agg.2/limbs` the program holds ONE reduce over the
    rows, whose six operands are the distinct columns (five values, one
    count) masked by group; no limb column is built or concatenated, and
    with six groups there is no contraction; the attempt says so. Before PR
    27: five contractions of 8 stacked f32 limbs each over 1,024 groups, 520
    of Q1's 548 ms at SF10 on a v5e."""
    text, infos = lowered[1], _last_attempt(ran[1][1]).infos
    stacks = [p.split("/") for p in SCOPED.findall(text) if "/limbs/" in p]
    assert stacks and all("sr.agg.2" in s for s in stacks)
    ops = {s[-1] for s in stacks}
    assert "reduce" in ops, sorted(ops)
    assert not [o for o in ops if o.startswith(("dot_general", "concatenate"))]
    over_rows = [line for line in text.splitlines()
                 if "stablehlo.reduce" in line and "x60416x" in line]
    assert len(over_rows) == 1 and over_rows[0].count("init:") == 6
    assert infos["segment_sums"] == {"sr.agg.2": {
        "rows": 60416, "groups": 6, "columns": 16, "distinct": 6,
        "limbs": 0, "formulation": "masked"}}


@pytest.mark.parametrize("q,has_limbs", [(3, True), (6, False)])
def test_limbs_phase_is_there_where_sums_are_batched(lowered, q, has_limbs):
    """Q6 has one group: a global masked reduction, no `limbs` phase. Q3
    groups by `l_orderkey`: at this scale its capacity of 1,024 is within
    `matmul_segsum_groups_max`, so its two sums are one contraction under
    `sr.agg.2/limbs` (at SF10, 129,024 groups: the lexsort's prefix sums,
    no `limbs`)."""
    limbs = [p for p in SCOPED.findall(lowered[q]) if "/limbs/" in p]
    assert bool(limbs) == has_limbs
    assert all("sr.agg.2" in p for p in limbs)


# what `chip_smoke.py` printed on a v5e at SF10 (PERF.md section 6): Q1
# `masked` over 6 groups, Q6 `global`, Q3 `sorted` (129,024 groups). Q3
# reaches `sorted` once its groups exceed `matmul_segsum_groups_max`: here
# the limit is set under the fixture's 1,024 for that case.
CHIP_FORMULATIONS = [
    (1, None, "sr.agg.2", "masked", 6),
    (6, None, "sr.agg.1", "global", 1),
    (3, None, "sr.agg.2", "contract", 1024),
    (3, 512, "sr.agg.2", "sorted", 1024),
]


@pytest.mark.parametrize(
    "q,matmul_max,scope,formulation,groups", CHIP_FORMULATIONS,
    ids=[f"q{q}-{f}" for q, _, _, f, _ in CHIP_FORMULATIONS])
def test_tpch_programs_take_the_chips_formulations(
        tpch, ran, q, matmul_max, scope, formulation, groups):
    """Tier-1 runs the ladder the chip runs: no backend test stands
    between a statement and its formulation, only its group count. With
    the limit moved the rows stay those of the default program (held to
    pandas in test_tpch_sql.py)."""
    result = ran[q][1]
    if matmul_max is not None:
        default = config.get("matmul_segsum_groups_max")
        config.set("matmul_segsum_groups_max", matmul_max)
        try:
            result = Session(tpch).sql(QUERIES[q])
        finally:
            config.set("matmul_segsum_groups_max", default)
        assert result.rows() == ran[q][1].rows()
    took = _last_attempt(result).infos["segment_sums"][scope]
    assert (took["formulation"], took["groups"]) == (formulation, groups)


def test_q3_profile_names_each_compaction(ran):
    """Beside an attempt's `capacities`: what each compaction of its program
    shrank (rows in, slots out) and how the index was computed; on a
    program-cache hit too (`ran` holds the second send)."""
    attempt = _last_attempt(ran[3][1])
    done = attempt.infos["compactions"]
    assert any(key.startswith("shrink_") for key in done)
    for key, c in done.items():
        assert c["out_cap"] == attempt.infos["capacities"][key] < c["cap"]
        assert c["method"] == INDEX_METHOD


@pytest.mark.parametrize("q", sorted(EXPECTED))
def test_scopes_info_reads_the_lowered_text_back_to_the_plan(ran, lowered, q):
    _, result, _ = ran[q]
    table = result.profile.infos["scopes"]
    numbered = set(re.findall(r"sr\.([a-z]+)\.(\d+)", " ".join(
        SCOPED.findall(lowered[q]))))
    assert numbered
    heads = {"scan": "Scan", "filter": "Filter", "project": "Project",
             "join": "Join", "agg": "Agg", "sort": "Sort", "limit": "Limit"}
    for kind, n in numbered:
        assert table[int(n)].startswith(heads[kind] + "["), (kind, n)
    assert all(len(text) <= 80 for text in table.values())
    # pre-order: 0 is the root, and the numbers have no holes
    assert sorted(table) == list(range(len(table)))
    assert table[0] == repr(result.plan)[:80]


@pytest.mark.parametrize("q", sorted(EXPECTED))
def test_ordinals_capacities_and_explain_analyze_are_the_parents(ran, q):
    _, result, text = ran[q]
    want = PARENT[f"q{q}"]
    prof = result.profile
    assert sorted([o, repr(n)] for n, o in prof.node_ord.items()) == \
        want["node_ord"]
    keys = {k for attempt in prof.children
            for k in attempt.infos.get("capacities") or {}}
    assert sorted(keys) == want["capacity_keys"]
    # the plan tree with its [#o est= rows= cap= ctrs{}] annotations; the
    # profile under it holds timings and is not compared
    assert text.split("\nquery:")[0] == want["tree"]


def _flat_spans(profile) -> list:
    out = list(profile.spans)
    for c in profile.children:
        out.extend(_flat_spans(c))
    return out


def _inside(inner, outer, slack=2e-3) -> bool:
    """Span starts are epoch stamps, durations come from the performance
    counter: allow the two clocks a little."""
    return (inner[1] >= outer[1] - slack
            and inner[1] + inner[2] <= outer[1] + outer[2] + slack)


def test_first_send_splits_compile_and_every_send_splits_the_run(tpch):
    s = Session(tpch)
    first = {}
    for n, t, d in _flat_spans(s.sql(QUERIES[6]).profile):
        first.setdefault(n, []).append((n, t, d))
    assert {"jax_trace", "jax_lower", "xla_compile", "dispatch",
            "device_wait", "compile_first_run"} <= set(first)
    (whole,) = first["compile_first_run"]
    for name in ("jax_trace", "jax_lower", "xla_compile"):
        assert len(first[name]) == 1 and _inside(first[name][0], whole), name
    # trace, lowering and compile happen inside the first call
    assert _inside(first["xla_compile"][0], first["dispatch"][0])
    t_trace, t_lower, t_xla = (first[n][0][1] for n in (
        "jax_trace", "jax_lower", "xla_compile"))
    assert t_trace <= t_lower + 2e-3 and t_lower <= t_xla + 2e-3

    warm = s.sql(QUERIES[6]).profile
    spans = {n: (n, t, d) for n, t, d in _flat_spans(warm)}
    assert not {"jax_trace", "jax_lower", "xla_compile",
                "compile_first_run"} & set(spans)
    run, dispatch, wait = (spans[n] for n in (
        "compile_and_run", "dispatch", "device_wait"))
    assert _inside(dispatch, run) and _inside(wait, run)
    assert dispatch[1] + dispatch[2] <= wait[1] + 2e-3  # disjoint, in order
    attempt = warm.children[0]
    assert {"dispatch", "device_wait"} <= set(attempt.counters)


def test_two_threads_compiling_keep_their_spans_apart():
    cat = Catalog()
    cat.register("t", HostTable.from_pydict(
        {"k": list(range(4000)), "v": [i % 7 for i in range(4000)]}))
    statements = ["select sum(v) a from t where k < 1234",
                  "select k, v from t where v = 3 order by k limit 5"]
    profiles, gate = [None, None], threading.Barrier(2)

    def send(i):
        s = Session(cat)
        gate.wait()
        profiles[i] = s.sql(statements[i]).profile

    threads = [threading.Thread(target=send, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for prof in profiles:
        by = {}
        for n, t, d in _flat_spans(prof):
            by.setdefault(n, []).append((n, t, d))
        # one fresh program each: its own trace, lowering and compile, once
        assert [len(by[n]) for n in ("jax_trace", "jax_lower", "xla_compile")
                ] == [1, 1, 1], {n: len(v) for n, v in by.items()}
        (whole,) = by["compile_first_run"]
        assert all(_inside(by[n][0], whole)
                   for n in ("jax_trace", "jax_lower", "xla_compile"))
    assert profiles[0].infos["program"] != profiles[1].infos["program"]


@pytest.mark.parametrize("knob", ["enable_sort_timing",
                                  "enable_device_profile"])
def test_removed_knobs_are_unknown(knob):
    with pytest.raises(Exception, match="(?i)unknown|no such|not defined"):
        Session().sql(f"set {knob} = true")


def test_profile_timers_are_host_events_of_a_profiler_trace(tpch, tmp_path):
    from jax.profiler import ProfileData

    s = Session(tpch)
    s.sql(QUERIES[6])  # compiled before the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        s.sql(QUERIES[6])
    finally:
        jax.profiler.stop_trace()
    (path,) = [os.path.join(d, f) for d, _, files in os.walk(tmp_path)
               for f in files if f.endswith(".xplane.pb")]
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"sr:optimize", "sr:fetch_results", "sr:compile_and_run",
            "sr:dispatch", "sr:device_wait"} <= names


def test_program_is_named_after_the_statements_fingerprint(tpch):
    saved = config.get("plan_feedback")
    try:
        for feedback in (True, False):
            config.set("plan_feedback", feedback)
            s = Session(tpch)
            r = s.sql(QUERIES[6])
            name = r.profile.infos["program"]
            assert re.fullmatch(r"q_[0-9a-f]{8}", name)
            (fn, _), = [prog for bucket in s.cache.programs.values()
                        for prog in bucket["progs"].values()]
            assert fn.__name__ == name  # the XLA module is jit_<name>
            if feedback:
                # ... and leads to the statement's row in SHOW WORKLOAD
                from starrocks_tpu.runtime.workload import WORKLOAD

                assert any(row["fingerprint"].startswith(name[2:])
                           for row in WORKLOAD.snapshot())
    finally:
        config.set("plan_feedback", saved)
