"""chip_smoke.py's own logic, exercised on CPU before chip time is spent:
the entry point refuses anything but a TPU, and its body — the served path
(MySQL + HTTP doors over one tier), the oracle comparison and the compile
accounting — passes at SF0.01."""

import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "'cpu'" in out.stderr, out.stderr[-2000:]
    # refused before any data was generated, and no result line printed
    assert time.monotonic() - t0 < 60
    assert "generated" not in out.stdout and '"ok"' not in out.stdout


def test_run_passes_on_cpu_at_small_scale():
    sys.path.insert(0, REPO)
    import chip_smoke

    res = chip_smoke.run(sf=0.01, chips=1, seed=7)
    assert res["ok"], res["failures"]
    assert res["device"]["platform"] == "cpu"
    by_name = {s["statement"]: s for s in res["statements"]}
    assert set(by_name) == {"mysql:q1", "mysql:q6", "mysql:q3", "http:q1"}
    for s in by_name.values():
        assert s["oracle_match"] and s["sends"][-1]["compiles"] == 0
        # one chip places nothing by hash
        assert all(send["hash_placements"] == send["hash_layouts"] == 0
                   for send in s["sends"])
    # a statement new to the tier compiles; the same statement through the
    # other door reuses the tier's program
    assert by_name["mysql:q1"]["sends"][0]["compiles"] >= 1
    assert by_name["http:q1"]["sends"][0]["compiles"] == 0
    assert res["resident_bytes"] > 0
    # Q3's program compacts, and the smoke says how; Q1's and Q6's do not
    assert by_name["mysql:q3"]["compactions"]["shrink_0mwb"]["method"]
    assert "compactions" not in by_name["mysql:q6"]
    # every aggregate's integer sums are one batch, and the smoke says what
    # it was: Q1's six groups, its five value columns and the one count each
    # summed once by the masked reduction, here as on the chip
    q1 = by_name["mysql:q1"]["segment_sums"]["sr.agg.2"]
    assert (q1["groups"], q1["distinct"], q1["formulation"]) == (
        6, 6, "masked")
    assert q1["columns"] > q1["distinct"]
    assert by_name["mysql:q6"]["segment_sums"]["sr.agg.1"][
        "formulation"] == "global"
    # Q3's groups: `sorted` at SF10 on the chip (129,024 groups); at this
    # scale its capacity is within the contraction's limit
    assert by_name["mysql:q3"]["segment_sums"]["sr.agg.2"][
        "formulation"] == "contract"


def test_ssb_flat_suite_passes_on_cpu_at_small_scale():
    """`--suite ssb_flat`: upstream's 13 flat-table statements, the
    benchmark's own text against the benchmark's own references, through the
    MySQL door; per statement the program's name, capacities, compactions
    and segment sums (the scopes' device times need a device trace: a CPU
    has none)."""
    sys.path.insert(0, REPO)
    import chip_smoke

    res = chip_smoke.run_ssb_flat(sf=0.05, seed=42)
    assert res["ok"], res["failures"]
    by_name = {s["statement"]: s for s in res["statements"]}
    assert list(by_name) == [f"ssb_flat.q{a}.{b}" for a, n in
                             ((1, 3), (2, 3), (3, 4), (4, 3))
                             for b in range(1, n + 1)]
    for s in by_name.values():
        assert s["oracle_match"] and s["sends"][-1]["compiles"] == 0
        # sums over integers: equal, not within the comparison's 1e-6
        assert s["integers_equal"]
        assert s["program"].startswith("q_") and s["scopes"] == {}
    # Q1.x are one global sum and compact nothing; Q2.1 compacts the rows
    # its filter keeps before year() and the GROUP BY see them
    q1, q2 = by_name["ssb_flat.q1.1"], by_name["ssb_flat.q2.1"]
    assert q1["compactions"] == {} and q1["segment_sums"]["sr.agg.1"][
        "formulation"] == "global"
    (shrink,) = q2["compactions"].values()
    assert shrink["live"] <= shrink["out_cap"] < shrink["cap"]
    assert q2["capacities"]["shrink_0"] == shrink["out_cap"]
    assert q2["segment_sums"]["sr.agg.2"]["formulation"] == "contract"


def test_scope_path_keeps_what_lies_under_the_plan_node():
    sys.path.insert(0, REPO)
    import chip_smoke

    assert chip_smoke._scope_path(
        "jit(q_1a2b3c4d)/sr.sort.0/sr.agg.2/datepart/year/jit(floor_divide)"
        "/div:") == "sr.agg.2/datepart/year"
    assert chip_smoke._scope_path(
        "jit(q_1a2b3c4d)/sr.agg.2/compact/gather/gather:"
    ) == "sr.agg.2/compact/gather"
    assert chip_smoke._scope_path("inputs[0][0][2]:") == "(no sr scope)"
    assert chip_smoke._scope_path(None) == "(no sr scope)"
