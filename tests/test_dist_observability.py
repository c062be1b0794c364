"""The four-backend deployment (`Session(dist_shards=4)`, the benchmark's
`tpch_sf10_x4`) on four virtual CPU devices at SF0.01: right against the
pandas oracle and the one-chip Session, its shards adding up to the whole,
and its fragment path named, timed and counted as the one-chip path is —
module names `q_<8 hex>_f<fid>`, compile spans in the statement's profile,
`exchange/pack` and `exchange/collective` scopes in the compiled text, and
the three exchange counters moving by what the shapes give. The listed cell
`tpch_sf10_x4.join` is rehearsed here too, through `benchmarks/run.py`'s
`run_cell`, timed and traced."""

import json
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pandas as pd
import pytest

import starrocks_tpu.sql.distributed as D
from starrocks_tpu.cache.keys import fragment_program_key
from starrocks_tpu.column import HostTable
from starrocks_tpu.ops.aggregate import PARTIAL, hash_aggregate
from starrocks_tpu.runtime.metrics import metrics
from starrocks_tpu.runtime.profile import RuntimeProfile
from starrocks_tpu.runtime.session import Session
from starrocks_tpu.sql.logical import LAggregate, walk_plan
from starrocks_tpu.sql.physical import Caps
from starrocks_tpu.storage.catalog import Catalog, tpch_catalog

from test_tpch_sql import _cmp_rows
from tpch_oracle import ORACLES, load_frames
from tpch_queries import QUERIES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 4
COUNTERS = ("sr_tpu_exchanges_total", "sr_tpu_exchange_slots_total",
            "sr_tpu_exchange_bytes_total")
NAME = re.compile(r"q_[0-9a-f]{8}_f(\d+)\Z")


@pytest.fixture(scope="module")
def small_tables_shard(eight_devices):
    """At SF0.01 every TPC-H table is under the engine's 100,000-row floor
    for sharding; lowered to 1,000 rows, `customer`, `orders` and `lineitem`
    are placed as they are at SF10 (hash of their distribution column)."""
    old = D.SHARD_THRESHOLD_ROWS
    D.SHARD_THRESHOLD_ROWS = 1_000
    yield
    D.SHARD_THRESHOLD_ROWS = old


@pytest.fixture(scope="module")
def tpch():
    return tpch_catalog(0.01)


@pytest.fixture(scope="module")
def dist(tpch, small_tables_shard):
    return Session(tpch, dist_shards=N)


@pytest.fixture(scope="module")
def local(tpch):
    return Session(tpch)


@pytest.fixture(scope="module")
def sends(dist):
    """q -> the profiles' owners of three sends of Q3 and Q1: the first
    compiles, the third is warm (a join is warm from its third send)."""
    return {q: [dist.sql(QUERIES[q]) for _ in range(3)] for q in (3, 1)}


def _counters() -> dict:
    values = metrics.snapshot_values()
    return {name: values[name][1] for name in COUNTERS}


def _moved(before: dict) -> dict:
    return {k: v - before[k] for k, v in _counters().items()}


def _spans(profile) -> list:
    out = list(profile.spans)
    for child in profile.children:
        out += _spans(child)
    return out


def _fragments(session, result):
    """(ir, plan, {fid: output chunk}, {fid: (jitted fn, its arguments)}) of
    the statement `result` ran, fragment by fragment as the executor's
    attempt runs them, at the capacities it published (so every program is
    a cache hit)."""
    de = session._dist_executor
    ir, scans_meta = de._fragment_ir(result.plan, RuntimeProfile("t"))
    plan, caps, p = ir.plan, Caps({}), RuntimeProfile("t")
    inputs = de._place(scans_meta)
    outputs, programs = {}, {}
    for frag in ir.fragments:
        bnd = tuple(outputs[d] for d in frag.deps)
        outputs[frag.fid], _, _ = de._fragment_attempt(
            plan, frag, caps, p, inputs, bnd, scans_meta, f"t_f{frag.fid}")
        bucket = de.cache.program_bucket(
            fragment_program_key(de.n, plan, frag))
        fn, _ = de.cache.bucket_prog_get(
            bucket, tuple(sorted(caps.values.items())))
        programs[frag.fid] = (fn, (inputs, bnd))
    assert not [n for n, _, _ in _spans(p) if n == "compile_first_run"]
    return ir, plan, outputs, programs


def _shards(chunk) -> list:
    """A sharded chunk as one chunk a shard, each on its own device."""
    return [jax.tree_util.tree_map(lambda a: a.addressable_shards[i].data,
                                   chunk) for i in range(N)]


def _frame(chunk) -> pd.DataFrame:
    return HostTable.from_chunk(chunk).to_pandas()


# --- right, and the shards add up to the whole ------------------------------

@pytest.mark.parametrize("q", [3, 1])
def test_four_shards_equal_the_oracle_and_one_chip(sends, local, tpch, q):
    got = sends[q][-1].rows()
    exp = [tuple(r) for r in ORACLES[q](load_frames(tpch))
           .itertuples(index=False)]
    _cmp_rows(got, exp, q, ordered=q == 1)
    _cmp_rows(got, local.sql(QUERIES[q]).rows(), q, ordered=q == 1)
    assert sends[q][-1].profile.infos["fragments"] >= 2
    assert all(r.rows() == got for r in sends[q])


def test_q1_partial_aggregates_of_the_shards_sum_to_the_whole(
        dist, local, sends):
    """Fragment 0 hands each shard its filtered rows; the engine's PARTIAL
    aggregate of each shard's rows, summed over the shards on the host, is
    the unsharded statement's answer."""
    _, plan, outputs, _ = _fragments(dist, sends[1][-1])
    agg = next(n for n in walk_plan(plan) if isinstance(n, LAggregate))
    keys = [name for name, _ in agg.group_by]
    parts = []
    for shard in _shards(outputs[0]):
        part, _ = hash_aggregate(shard, agg.group_by, agg.aggs, 1024,
                                 mode=PARTIAL)
        parts.append(_frame(part))
    assert all(len(part) for part in parts), "a shard held no row"
    whole = pd.concat(parts).groupby(keys).sum().sort_index()
    exp = local.sql(QUERIES[1]).to_pandas()
    exp = exp.sort_values(list(exp.columns[:2]))
    assert len(whole) == len(exp) == 4
    # the statement's columns after its two keys are its aggregates, in order
    for (name, spec), column in zip(agg.aggs, exp.columns[2:]):
        if spec.fn == "avg":  # its state is a sum and a count
            got = whole[name + "__sum"] / whole[name + "__cnt"]
        else:
            got = whole[name]
        np.testing.assert_allclose(got.to_numpy(float),
                                   exp[column].to_numpy(float), rtol=1e-9)


def test_q3_join_outputs_of_the_shards_unite_to_the_whole(dist, local, sends):
    """Before the final gather every group of Q3 lives on one shard: the
    union of the shards' outputs is the unsharded GROUP BY, no group twice,
    and so is the join below it (orders of BUILDING customers)."""
    ir, _, outputs, _ = _fragments(dist, sends[3][-1])
    assert [f.sink for f in ir.fragments] == [False, False, False, True]
    joined = [_frame(c) for c in _shards(outputs[1])]
    assert sum(len(j) > 0 for j in joined) == N
    got = pd.concat(joined)
    key = next(c for c in got.columns if c.endswith("o_orderkey"))
    exp = local.sql(
        "select o_orderkey from customer, orders where c_mktsegment = "
        "'BUILDING' and c_custkey = o_custkey "
        "and o_orderdate < date '1995-03-15'").to_pandas()
    assert sorted(got[key]) == sorted(exp["o_orderkey"])

    grouped = pd.concat(_frame(c) for c in _shards(outputs[2]))
    exp = local.sql(QUERIES[3].split("order by")[0]).to_pandas()
    assert grouped["l_orderkey"].is_unique
    assert len(grouped) == len(exp)
    both = grouped.merge(exp, on="l_orderkey", suffixes=("", "_exp"))
    assert len(both) == len(exp)
    np.testing.assert_allclose(both["revenue"].to_numpy(float),
                               both["revenue_exp"].to_numpy(float), rtol=1e-9)


# --- named and timed like the one-chip path ----------------------------------

def test_every_fragment_program_of_q3_has_its_own_module_name(dist, sends):
    result = sends[3][-1]
    names = result.profile.infos["program"]
    assert len(names) == len(set(names)) == 4
    assert [int(NAME.match(n).group(1)) for n in names] == [0, 1, 2, 3]
    head = names[0].rsplit("_f", 1)[0]
    assert all(n.startswith(head + "_f") for n in names)

    _, _, _, programs = _fragments(dist, result)
    scoped = {}
    for fid, (fn, args) in programs.items():
        text = fn.lower(*args).compile().as_text()
        # (a parameter carries its argument's path, `inputs[1][0][2]`, and
        # the scalar body of a reduction its stack less the module)
        ops = [op for op in re.findall(r'op_name="([^"]+)"', text)
               if op.startswith("jit(")]
        assert ops, fid
        # the module's name is the first jit(...) of every operation's stack
        assert {op.split("/")[0] for op in ops} == {f"jit({names[fid]})"}
        scoped[fid] = {"/".join(c for c in op.split("/")[:-1]
                                if c.startswith("sr.")
                                or c in ("exchange", "pack", "collective"))
                       for op in ops}
    flat = set().union(*scoped.values())
    under = {s for s in flat if "/exchange/" in s}
    assert any(re.search(r"sr\.join\.\d+/exchange/pack\Z", s)
               for s in under), sorted(under)
    assert any(re.search(r"sr\.join\.\d+/exchange/collective\Z", s)
               for s in under), sorted(under)
    # the result gather runs under the sort (top-10) that asked for it
    assert any(re.search(r"sr\.sort\.0/exchange/collective\Z", s)
               for s in scoped[3]), sorted(scoped[3])


def test_q1_gathers_its_partial_aggregates_under_the_aggregate(dist, sends):
    _, _, _, programs = _fragments(dist, sends[1][-1])
    fn, args = programs[1]
    ops = re.findall(r'op_name="([^"]+)"', fn.lower(*args).compile().as_text())
    gathers = [op for op in ops if op.endswith("/all_gather")]
    assert gathers and all(re.search(
        r"/sr\.agg\.\d+/exchange/collective/all_gather\Z", op)
        for op in gathers), gathers


@pytest.mark.parametrize("q", [3, 1])
def test_fresh_fragment_statement_splits_its_compile_and_a_warm_one_has_none(
        sends, q):
    first, _, warm = sends[q]
    names = [n for n, _, _ in _spans(first.profile)]
    fragments = first.profile.infos["fragments"]
    for span in ("jax_trace", "jax_lower", "xla_compile"):
        assert names.count(span) >= fragments, (span, names)
    assert names.count("compile_first_run") >= fragments
    assert names.count("dispatch") == names.count("device_wait") >= fragments
    for name, _, seconds in _spans(first.profile):
        assert seconds >= 0, name
    warm_names = [n for n, _, _ in _spans(warm.profile)]
    assert not {"jax_trace", "jax_lower", "xla_compile",
                "compile_first_run"} & set(warm_names)
    assert warm_names.count("dispatch") == fragments
    # the per-fragment timers stay: compile on the fresh send, execute warm
    assert {f"fragment_{i}_compile" for i in range(fragments)} <= set(names)
    assert {f"fragment_{i}_execute" for i in range(fragments)} <= set(
        warm_names)
    for result in (first, warm):
        infos = result.profile.infos
        assert len(infos["program"]) == fragments
        assert infos["scopes"][0] == repr(result.plan)[:80]
        assert infos["query_id"] > 0
        attempt = result.profile.children[-1].infos
        assert attempt["n_shards"] == N and "capacities" in attempt
    assert first.profile.infos["program"] == warm.profile.infos["program"]
    assert first.profile.infos["query_id"] != warm.profile.infos["query_id"]


def test_q3_attempt_names_its_compactions_and_how_full_its_exchanges_ran(sends):
    for result in sends[3]:
        attempt = result.profile.children[-1].infos
        done = attempt["compactions"]
        # the top-10 of each shard is compacted to 1,024 slots before the
        # result gather, lineitem's 15,360 slots a shard to their live
        # rows before the search (`orders`' 3,840 are under the rule's
        # 8,192); on a cache hit the info is read from the bucket
        # (the first send also shrinks the join's build side, which the
        # learned shuffle capacity then leaves under 8,192 slots)
        assert {"topn_0", "shrink_3l"} <= set(done) <= {
            "topn_0", "shrink_3l", "shrink_3r"}
        assert done["topn_0"]["out_cap"] == 1024 < done["topn_0"]["cap"]
        assert "live" not in done["topn_0"]  # bounded by its caller
        probe = done["shrink_3l"]
        assert 0 < probe["live"] <= probe["out_cap"] < probe["cap"] == 15360
        fill = attempt["exchange_fill"]
        assert {k.split("_")[0] for k in fill} == {"shufL", "shufR"}
        for key, share in fill.items():
            assert 0 < share <= 1
            assert attempt["capacities"][key] >= 1024


def test_monolithic_program_takes_the_plain_name(dist, sends):
    from starrocks_tpu.runtime.config import config

    config.set("dist_fragments", False)
    try:
        first = dist.sql(QUERIES[1])
        again = dist.sql(QUERIES[1])
    finally:
        config.set("dist_fragments", True)
    # (`dist_fragments` is part of the statement's fingerprint, so the hex
    # is not the fragment programs')
    assert re.fullmatch(r"q_[0-9a-f]{8}", *first.profile.infos["program"])
    assert again.profile.infos["program"] == first.profile.infos["program"]
    assert first.profile.infos["scopes"] == sends[1][0].profile.infos["scopes"]
    assert "xla_compile" in [n for n, _, _ in _spans(first.profile)]
    assert "xla_compile" not in [n for n, _, _ in _spans(again.profile)]
    assert again.rows() == sends[1][-1].rows()


# --- counted from the shapes ---------------------------------------------------

@pytest.fixture(scope="module")
def hand(small_tables_shard):
    """Two tables of 4,000 rows of two int64 columns, no declared
    distribution: 1,000 rows a shard in 1,024 slots."""
    n = 4000
    cat = Catalog()
    cat.register("t1", HostTable.from_pydict(
        {"k": np.arange(n), "a": np.arange(n) * 3}))
    cat.register("t2", HostTable.from_pydict(
        {"j": np.arange(n)[::-1].copy(), "b": np.arange(n) * 5}))
    return cat


def test_counters_move_by_what_the_shapes_give(hand):
    s = Session(hand, dist_shards=N)
    # a broadcast: each shard's top 3 ride in its 1,024 slots (no compaction:
    # the shard has no more) of k, a and the live mask = 17 bytes a slot, to
    # the three other shards
    before = _counters()
    result = s.sql("select k, a from t1 order by a desc limit 3")
    assert result.rows() == [(3999, 11997), (3998, 11994), (3997, 11991)]
    assert _moved(before) == {
        "sr_tpu_exchanges_total": 1, "sr_tpu_exchange_slots_total": 1024,
        "sr_tpu_exchange_bytes_total": 1024 * (8 + 8 + 1) * 3}

    # a shuffle join: both sides hash-partitioned into 4 buckets of C slots
    # (k or j, a or b, live mask = 17 bytes a slot; one bucket stays home),
    # then one slot of partial aggregates (count, sum, the sum's validity,
    # live mask = 18 bytes) gathered from every shard
    sql = "select count(*) c, sum(a + b) s from t1 join t2 on k = j"
    for send in range(3):  # compiled, compiled at tightened capacities, warm
        before = _counters()
        result = s.sql(sql)
        assert result.rows() == [(4000, 63984000)]
        caps = result.profile.children[-1].infos["capacities"]
        cl, cr = caps["shufL_2"], caps["shufR_2"]
        assert _moved(before) == {
            "sr_tpu_exchanges_total": 3,
            "sr_tpu_exchange_slots_total": N * cl + N * cr + 1,
            "sr_tpu_exchange_bytes_total":
                cl * 17 * (N - 1) + cr * 17 * (N - 1) + 18 * (N - 1)}, send
        fill = result.profile.children[-1].infos["exchange_fill"]
        # 1,000 rows a shard over four buckets of C slots
        assert fill == pytest.approx(
            {"shufL_2": 250 / cl, "shufR_2": 250 / cr}, rel=0.2)


def test_counters_stay_put_on_one_chip(hand, local):
    before = _counters()
    assert Session(hand).sql(
        "select count(*) c from t1 join t2 on k = j").rows() == [(4000,)]
    local.sql(QUERIES[3])
    assert set(_moved(before).values()) == {0}


def test_exchange_shapes_by_hand():
    """`parallel/exchange.py` on a mesh of four, eight rows a shard: what
    each exchange appends to its log."""
    from jax.sharding import PartitionSpec as P

    from starrocks_tpu import types as T
    from starrocks_tpu.column.column import Chunk, Field, Schema
    from starrocks_tpu.exprs.ir import Col
    from starrocks_tpu.parallel.exchange import (
        all_gather_chunk, range_partition_chunk, shuffle_chunk)
    from starrocks_tpu.parallel.mesh import make_mesh, shard_map

    mesh = make_mesh(N)
    rows = 8 * N
    k = jax.numpy.arange(rows, dtype=jax.numpy.int64)
    v = jax.numpy.arange(rows, dtype=jax.numpy.int32)
    ok = k % 3 != 0
    schema = Schema((Field("k", T.BIGINT, False), Field("v", T.INT, True)))
    log: list = []

    def step(k, v, ok):
        chunk = Chunk(schema, (k, v), (None, ok), None)
        shuffled, full = shuffle_chunk(chunk, (Col("k"),), "d", N, 4,
                                       log=log, check="shuf_0")
        gathered = all_gather_chunk(shuffled, "d", log=log)
        ranged, _ = range_partition_chunk(chunk, k, "d", N, 8, 2, log=log,
                                          check="sort_0")
        return (gathered.num_rows()[None], ranged.num_rows()[None],
                full[None])

    spec = P("d")
    n_gathered, n_ranged, full = shard_map(
        step, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(spec, spec, spec))(k, v, ok)
    # k int64 + v int32 + v's validity + the live mask
    row = 8 + 4 + 1 + 1
    assert log == [
        {"op": "all_to_all", "slots": 16, "bytes": 4 * row * 3,
         "check": "shuf_0"},
        # the shuffled chunk's 16 slots, as they are, to three shards
        {"op": "all_gather", "slots": 16, "bytes": 16 * row * 3,
         "check": None},
        # a range exchange is two: 2 sample ranks, then its all_to_all
        {"op": "all_gather", "slots": 2, "bytes": 2 * 8 * 3, "check": None},
        {"op": "all_to_all", "slots": 32, "bytes": 8 * row * 3,
         "check": "sort_0"}]
    # nothing was lost on the way, whatever bucket a row fell into
    if int(full.max()) <= 4:
        assert set(np.asarray(n_gathered)) == {rows}
    assert int(np.asarray(n_ranged).sum()) == rows


# --- the listed cell, rehearsed -------------------------------------------------

_RUN = """
import json, sys
sys.path.insert(0, {root!r})
import starrocks_tpu.sql.distributed as D
D.SHARD_THRESHOLD_ROWS = 1000   # SF0.01 tables placed as SF10's are
from benchmarks import run
from benchmarks.harness import sut
seen = []
counters = sut.System.counters
sut.System.counters = staticmethod(lambda: seen.append(counters()) or seen[-1])
result = run.run_cell("tpch_sf10_x4.join", {seed}, 1.5, {traced}, scale=0.01)
name = "sr_tpu_exchange_bytes_total"
print(json.dumps({{"result": result,
                   "window_bytes": seen[-1][name] - seen[-2][name]}}))
"""


@pytest.mark.parametrize("traced", [False, True], ids=["timed", "traced"])
def test_listed_cell_runs_on_four_virtual_devices(traced):
    """`tpch_sf10_x4.join` as BENCHMARK.json lists it, against the repo's own
    root, at SF0.01 on four virtual CPU devices: the rehearsal
    benchmarks/tests cannot make for a listed cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"]
                if w["name"] == "tpch_sf10_x4.join")
    assert cell["chips"] == N and cell["traffic"] == "join_scan_cycle"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={N}")
    out = subprocess.run(
        [sys.executable, "-c",
         _RUN.format(root=ROOT, seed=2600000011 + traced, traced=traced)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    result, window_bytes = last["result"], last["window_bytes"]
    assert result["correct"] and result["failed"] == 0, out.stdout[-3000:]
    assert result["attempted"] >= 4  # two cycles of Q3 then Q1
    assert result["device"]["count"] == N
    got = {name: m["value"] for name, m in result["metrics"].items()}
    if not traced:
        assert set(got) == {"setup_s", "lat_geomean_ms"}  # no HBM on a CPU
        assert all(v > 0 for v in got.values())
        return
    # a CPU trace has no device plane: what only it can give is left out
    per_layer = [m for m in bench["per_layer"]
                 if "tpch_sf10_x4.join" in m.get("workloads",
                                                 ["tpch_sf10_x4.join"])
                 and m["moves"] in ("setup_s", "lat_geomean_ms",
                                    "peak_hbm_gb")]
    assert set(got) == {m["name"] for m in per_layer
                        if m["source"] != "device_trace"}
    assert {"collective_share", "op_exchange_ms"} <= {
        m["name"] for m in per_layer}
    assert got["window_compiles"] == 0
    assert got["jax_trace_s"] > 0 and got["xla_compile_s"] > 0
    assert got["dispatch_ms"] > 0 and got["device_wait_ms"] > 0
    assert window_bytes > 0
    assert got["exchange_mb_per_stmt"] == pytest.approx(
        window_bytes / result["attempted"] / 1e6, rel=1e-12)


# --- the smoke the chips run ----------------------------------------------------

def test_chip_smoke_on_four_virtual_devices(small_tables_shard, capsys):
    """`chip_smoke.py --chips 4`'s body: the collective workarounds against
    numpy, Q1/Q6/Q3 over the wire against the oracle, and a line for every
    fragment program with its name, compactions and exchanges."""
    sys.path.insert(0, ROOT)
    import chip_smoke

    res = chip_smoke.run(sf=0.01, chips=N, seed=7)
    out = capsys.readouterr().out
    assert res["ok"], res["failures"]
    assert f"collective or_by_int32_psum shards={N} lanes=65536" in out
    assert out.count("mismatches=0") >= 2
    assert "collective plain " in out
    by_name = {s["statement"]: s for s in res["statements"]}
    for s in by_name.values():
        assert s["oracle_match"] and s["sends"][-1]["compiles"] == 0
    programs = by_name["mysql:q3"]["programs"]
    assert [int(NAME.match(n).group(1)) for n in programs] == [0, 1, 2, 3]
    shuffles = [e["check"] for holds in programs.values()
                for e in holds["exchanges"] if e["op"] == "all_to_all"]
    assert [c.split("_")[0] for c in shuffles] == ["shufL", "shufR"]
    assert by_name["mysql:q3"]["compactions"]["topn_0"]["method"]
    f2 = next(h for n, h in programs.items() if n.endswith("_f2"))
    assert 0 < f2["compactions"]["shrink_3l"]["live"]
    assert re.search(r"program mysql:q3 name=q_[0-9a-f]{8}_f2 compactions="
                     r'\{"shrink_3l": \{"cap": 15360, "out_cap": \d+, '
                     r'"method": "shift", "live": \d+\}', out)
    assert re.search(r"program mysql:q3 name=q_[0-9a-f]{8}_f3 compactions="
                     r'\{"topn_0"', out)
    assert len(by_name["mysql:q1"]["programs"]) == 2


def test_chip_smoke_fails_where_q3_searches_at_its_probe_capacity(
        small_tables_shard, monkeypatch):
    """`_f2` without a `shrink_<n>l` is the four-chip Q3 of before PR 30
    (20 s a statement at SF10): right answers, and a failure."""
    sys.path.insert(0, ROOT)
    import chip_smoke
    from starrocks_tpu.sql import physical

    assert chip_smoke._probe_shrinks(
        {"q_0_f1": {"compactions": {"shrink_6l": {}}},
         "q_0_f2": {"compactions": {"shrink_3r": {}, "shrink_3l": {},
                                    "topn_0": {}}}}, 2) == ["shrink_3l"]
    monkeypatch.setattr(physical, "SHRINK_MIN_CAPACITY", 1 << 30)
    res = chip_smoke.run(sf=0.01, chips=N, seed=7)
    assert not res["ok"]
    assert res["failures"] == [
        "mysql:q3: fragment _f2 compacts no probe side (no shrink_<n>l)"]
    by_name = {s["statement"]: s for s in res["statements"]}
    assert all(s["oracle_match"] for s in by_name.values())


def test_collective_check_reports_a_mismatch(monkeypatch, eight_devices):
    """The smoke's comparison itself: a workaround that returns wrong lanes
    is a failure, not a line."""
    sys.path.insert(0, ROOT)
    import chip_smoke
    from starrocks_tpu.ops import join

    monkeypatch.setattr(join, "_or_across_shards",
                        lambda lanes, axis: lanes)  # no merge at all
    failures = chip_smoke._check_collectives(N, 7)
    assert len(failures) == 1 and "uint8 OR" in failures[0]
