#!/usr/bin/env python
"""Chip smoke: TPC-H through the served path on a real TPU, or a failure.

    python chip_smoke.py [--chips 4] [--sf 10] [--seed 42]

The quickest proof that the system still starts on the chip. One process,
no children: `Session(tpch_catalog(sf, seed))` -> `ServingTier` ->
`MySQLServer(port=0)` + `SqlHttpServer(port=0)` as threads, then TPC-H Q1,
Q6, Q3 over the MySQL wire and Q1 again over HTTP, engine defaults untouched
(result cache off, strategies `auto`), so every send runs a device program.
Each statement is sent twice, and a third time only if the second send still
compiled (a first run that over-seeded a capacity publishes the tightened
one, and the next run compiles once at it — runtime/executor.py `_adaptive`);
the last send must compile nothing. Every answer is compared with
tests/tpch_oracle.py after the sends, by tests/test_tpch_sql.py's tolerance.

    python chip_smoke.py --suite ssb_flat [--sf 100]

runs upstream's 13 SSB flat-table statements instead (`benchmarks/statements/
ssb_flat/`, in upstream's text, over one chip's share of `lineorder_flat` from
`benchmarks/datagen/ssb_flat.py`, against `benchmarks/oracles/ssb_flat/`), the
same way through the MySQL door, and prints per statement its program's
name, `capacities`, `compactions`, `segment_sums`, `dict_predicates`, and
from a `jax.profiler` trace of one more send the device time of each scope
(`sr.agg.2/datepart/year`, `sr.agg.2/compact/gather`, ...), so that a slow
statement is named by scope and phase before a benchmark run is spent on it.

Exits non-zero — with no JSON line — unless `jax.default_backend()` is
"tpu"; no flag admits a CPU. Also non-zero on any mismatch, exception,
missing counter, compile in a last send, off-device column or missing
memory statistic. With `--chips 4` it first runs the two collectives the
engine works around (uint8 OR as an int32 psum, int64 min/max as an
all_gather) on the real mesh against numpy, prints every fragment
program's module name with its compactions (`cap`, `out_cap`, `live`) and
exchanges, and fails if Q3's `_f2` holds no `shrink_<n>l`. Each send's line
counts the scans it placed by hash (`hash_placements`) and the shard layouts
the host derived for them (`hash_layouts`); a last send that derives one
fails. Seconds are printed as facts of this run, not as metrics. Last stdout
line on success:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import faulthandler
import http.client
import json
import math
import numbers
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# (door, TPC-H query, result column that orders rows for comparison — None
# where the statement's own ORDER BY is total, as tests/test_tpch_sql.py's
# FULLY_ORDERED has it; Q3's top-10 may tie on its sort keys, so its rows
# are matched by the unique l_orderkey instead)
STATEMENTS = (("mysql", 1, None), ("mysql", 6, None), ("mysql", 3, 0),
              ("http", 1, None))

# the columns Q1/Q6/Q3 read: all the oracle's frames hold (a to_pandas of
# all 16 lineitem columns at SF10 is tens of GB of host strings) and the
# floor for what must be resident on the device afterwards
QUERY_COLUMNS = {
    "lineitem": ("l_orderkey", "l_quantity", "l_extendedprice", "l_discount",
                 "l_tax", "l_returnflag", "l_linestatus", "l_shipdate"),
    "orders": ("o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"),
    "customer": ("c_custkey", "c_mktsegment"),
}

MAX_SENDS = 3


def _cell_ok(got, exp) -> bool:
    """One wire cell (MySQL text or JSON value) against the oracle's value:
    numbers within 1e-6 relative, dates by day, everything else as text."""
    import numpy as np
    import pandas as pd

    if exp is None or (isinstance(exp, float) and math.isnan(exp)):
        return got is None
    if got is None:
        return False
    if isinstance(exp, (pd.Timestamp, np.datetime64)):
        return str(got)[:10] == str(exp)[:10]
    if isinstance(exp, (int, float, np.integer, np.floating)):
        g, e = float(got), float(exp)
        return abs(g - e) <= max(abs(e), 1.0) * 1e-6
    return str(got) == str(exp)


def _first_mismatch(got, exp, key):
    """None when the rows agree, else a description of the first difference."""
    if len(got) != len(exp):
        return f"{len(got)} rows vs oracle {len(exp)}"
    if key is not None:
        got = sorted(got, key=lambda r: int(r[key]))
        exp = sorted(exp, key=lambda r: int(r[key]))
    for i, (g, e) in enumerate(zip(got, exp)):
        if len(g) != len(e):
            return f"row {i}: arity {len(g)} vs {len(e)}"
        for j, (gv, ev) in enumerate(zip(g, e)):
            if not _cell_ok(gv, ev):
                return f"row {i} col {j}: {gv!r} vs oracle {ev!r}"
    return None


def _oracle_frames(catalog) -> dict:
    from starrocks_tpu.column import HostTable, Schema

    frames = {}
    for table, cols in QUERY_COLUMNS.items():
        ht = catalog.get_table(table).table
        sub = HostTable(
            Schema(tuple(f for f in ht.schema.fields if f.name in cols)),
            ht.arrays, {k: v for k, v in ht.valids.items() if k in cols})
        frames[table] = sub.to_pandas()
    return frames


def _last_attempt_info(name: str) -> dict:
    """The info `name` of the newest retained statement's last attempt that
    has it: `compactions` ({} where its program compacts nothing),
    `segment_sums` (an aggregate scope -> its batch of integer sums),
    `dict_predicates` (a plan-node scope -> its boolean predicates over
    dictionary columns, `ranges` or `lut`), and on
    a mesh `programs`, module name -> that fragment program's compactions and
    exchanges."""
    from starrocks_tpu.runtime.profile import PROFILE_MANAGER

    entries = PROFILE_MANAGER.snapshot()
    attempts = ((entries[-1]["profile"] or {}).get("children", ())
                if entries else ())
    done = [a["infos"][name] for a in attempts if name in a.get("infos", {})]
    return done[-1] if done else {}


def _last_statement_info(name: str):
    """The info `name` of the newest retained statement itself (`program`,
    its XLA module's name)."""
    from starrocks_tpu.runtime.profile import PROFILE_MANAGER

    entries = PROFILE_MANAGER.snapshot()
    return ((entries[-1]["profile"] or {}).get("infos", {}).get(name)
            if entries else None)


def _probe_shrinks(programs: dict, fid: int) -> list:
    """The keys `shrink_<n>l` among the compactions of fragment program
    `_f<fid>`: the probe sides of its joins that ran at their live rows."""
    return [key for module, holds in programs.items()
            if module.endswith(f"_f{fid}") for key in holds["compactions"]
            if re.fullmatch(r"shrink_\d+l", key)]


def _check_collectives(chips: int, seed: int) -> list:
    """The two collectives the engine works around, on the real mesh against
    numpy: a bitwise OR of uint8 lanes as an int32 psum, and an int64 min/max
    as an all_gather and a local reduce (ops/join.py). On a 2x2 v5e mesh
    `lax.pmax` on uint8 returned wrong lanes and int64 `pmin` did not
    compile (PR 21); virtual CPU devices show neither fault, so only this run
    holds the workarounds to the chips. What the plain collectives do today
    is printed as a fact and fails nothing. Returns the failures."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from starrocks_tpu.ops.join import (_min_max_across_shards,
                                        _or_across_shards)
    from starrocks_tpu.parallel.mesh import DATA_AXIS, make_mesh, shard_map

    rng = np.random.default_rng(seed)
    lanes = (rng.random((chips, 65536)) < 0.1).astype(np.uint8)
    bounds = rng.integers(-2**62, 2**62, size=(chips, 2), dtype=np.int64)
    mesh, spec = make_mesh(chips), P(DATA_AXIS)

    def on_mesh(step):
        return jax.jit(shard_map(step, mesh, in_specs=(spec, spec),
                                 out_specs=(spec, spec)))(lanes, bounds)

    def workarounds(lanes, bounds):
        lo, hi = _min_max_across_shards(bounds[0, 0], bounds[0, 1], DATA_AXIS)
        return (_or_across_shards(lanes[0], DATA_AXIS)[None],
                jnp.stack([lo, hi])[None])

    def plain(lanes, bounds):
        return (jax.lax.pmax(lanes[0], DATA_AXIS)[None],
                jnp.stack([jax.lax.pmin(bounds[0, 0], DATA_AXIS),
                           jax.lax.pmax(bounds[0, 1], DATA_AXIS)])[None])

    want_lanes = lanes.any(axis=0).astype(np.uint8)
    want_bounds = np.array([bounds[:, 0].min(), bounds[:, 1].max()])
    failures = []
    merged, reduced = (np.asarray(x) for x in on_mesh(workarounds))
    wrong_lanes = int((merged != want_lanes[None]).sum())
    wrong_bounds = int((reduced != want_bounds[None]).sum())
    print(f"collective or_by_int32_psum shards={chips} lanes=65536 "
          f"set={int(want_lanes.sum())} mismatches={wrong_lanes}")
    print(f"collective int64_min_max_by_all_gather shards={chips} "
          f"mismatches={wrong_bounds}")
    if wrong_lanes:
        failures.append(f"uint8 OR by int32 psum: {wrong_lanes} lanes differ "
                        "from numpy")
    if wrong_bounds:
        failures.append(f"int64 min/max by all_gather: {reduced.tolist()} vs "
                        f"numpy {want_bounds.tolist()}")
    try:
        merged, reduced = (np.asarray(x) for x in on_mesh(plain))
        print(f"collective plain pmax_uint8_mismatches="
              f"{int((merged != want_lanes[None]).sum())} "
              f"pmin_pmax_int64_mismatches="
              f"{int((reduced != want_bounds[None]).sum())} (not used)")
    except Exception as e:  # noqa: BLE001 — a fact of the backend, not a failure
        print(f"collective plain does_not_compile={type(e).__name__}: "
              f"{str(e).splitlines()[0][:160]} (not used)")
    return failures


def _send_until_warm(name: str, send, failures: list) -> tuple:
    """Send a statement twice, and a third time only if the second send
    still compiled; the last send must compile nothing. A send's record
    also counts its scans placed on a mesh by hash (`hash_placements`) and
    the shard layouts they derived on the host (`hash_layouts`), which the
    last send must not. Returns (the sends' records, the rows of the
    last)."""
    from starrocks_tpu.runtime.metrics import (HASH_LAYOUTS, HASH_PLACEMENTS,
                                               PROGRAM_COMPILES, RECOMPILES)

    sends, rows = [], None
    while len(sends) < 2 or (sends[-1]["compiles"] and len(sends) < MAX_SENDS):
        c0, r0 = PROGRAM_COMPILES.value, RECOMPILES.value
        p0, l0 = HASH_PLACEMENTS.value, HASH_LAYOUTS.value
        t0 = time.monotonic()
        got = send()
        sends.append({
            "seconds": round(time.monotonic() - t0, 3),
            "compiles": int(PROGRAM_COMPILES.value - c0),
            "recompiles": int(RECOMPILES.value - r0),
            "hash_placements": int(HASH_PLACEMENTS.value - p0),
            "hash_layouts": int(HASH_LAYOUTS.value - l0)})
        if rows is not None and got != rows:
            failures.append(f"{name}: send {len(sends)} returned other rows "
                            "than send 1")
        rows = got
        print(f"sent {name} #{len(sends)} rows={len(got)} "
              f"{json.dumps(sends[-1])}")
    if sends[-1]["compiles"] or sends[-1]["recompiles"]:
        failures.append(f"{name}: send {len(sends)} still compiled "
                        f"({sends[-1]['compiles']} programs)")
    if sends[-1]["hash_layouts"]:
        failures.append(f"{name}: send {len(sends)} derived "
                        f"{sends[-1]['hash_layouts']} shard layouts")
    return sends, rows


def _scope_path(tf_op) -> str:
    """`sr.agg.2/datepart/year` of `jit(q_..)/sr.sort.0/sr.agg.2/datepart/
    year/floor_divide:`: the name stack from the innermost plan node down,
    less the primitive."""
    stack = (tf_op or "").rsplit(":", 1)[0].split("/")
    inner = max((i for i, c in enumerate(stack) if c.startswith("sr.")),
                default=None)
    if inner is None:
        return "(no sr scope)"
    return "/".join(c for c in stack[inner:-1] if not c.startswith("jit("))


def _traced_scopes(send) -> dict:
    """{scope path: device self seconds} of one `send()` under
    `jax.profiler`; {} on a backend whose trace holds no device plane."""
    import tempfile

    import jax

    from benchmarks.harness import scopes, xplane

    with tempfile.TemporaryDirectory() as trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            send()
        finally:
            jax.profiler.stop_trace()
        devices = scopes.read_ops(xplane.find_xplane(trace_dir))
    totals: dict = {}
    for ops in devices.values():
        events = [(_scope_path(tf_op), start, end)
                  for _, tf_op, _, start, end in ops]
        for scope, self_ps in xplane._self_times(events):
            totals[scope] = totals.get(scope, 0.0) + self_ps / 1e12
    return totals


def run_ssb_flat(sf: float, seed: int) -> dict:
    """The 13 SSB flat-table statements through the MySQL door, on whatever
    backend JAX has (main() lets only a TPU through; tier-1 calls this at a
    small `sf` on CPU). Returns {"ok", "failures", "device", "statements"}."""
    import jax

    sys.path.insert(0, REPO)
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from test_mysql_protocol import FullClient

    from benchmarks.harness import cells, compare
    from starrocks_tpu.runtime.mysql_service import MySQLServer
    from starrocks_tpu.runtime.serving import ServingTier
    from starrocks_tpu.runtime.session import Session
    from starrocks_tpu.storage.catalog import Catalog

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"versions {json.dumps(_versions())}")
    print(f"device {json.dumps(device)} suite=ssb_flat sf={sf:g} seed={seed} "
          f"compile_cache={jax.config.jax_compilation_cache_dir}")
    flat = "ssb_flat_sf100_share.cycle13x10"
    cell = cells.Cell(REPO, flat)
    t0 = time.monotonic()
    gen = cells.load_module(REPO, "datagen", cell.config["generator"])
    tables = gen.generate(sf, seed)
    print(f"generated lineorder_flat_rows="
          f"{tables['lineorder_flat'].num_rows} "
          f"seconds={time.monotonic() - t0:.1f}")
    catalog = Catalog()
    for name, table in tables.items():
        catalog.register(name, table, gen.UNIQUE_KEYS.get(name, ()),
                         gen.DISTRIBUTION.get(name, ()))
    session = Session(catalog)
    my = MySQLServer(session, port=0, tier=ServingTier(session)).start()
    mysql = FullClient("127.0.0.1", my.port)
    mysql.sock.settimeout(1100)

    failures: list = []
    statements = []
    try:
        for v in cell.variants:
            name = v["template"]

            def send(sql=v["sql"]):
                return mysql.query(sql)[1]

            sends, rows = _send_until_warm(name, send, failures)
            record = {"statement": name, "sends": sends,
                      "program": _last_statement_info("program"),
                      "capacities": _last_attempt_info("capacities"),
                      "compactions": _last_attempt_info("compactions"),
                      "segment_sums": _last_attempt_info("segment_sums"),
                      "dict_predicates": _last_attempt_info(
                          "dict_predicates")}
            for key in ("program", "capacities", "compactions",
                        "segment_sums", "dict_predicates"):
                print(f"{key} {name} {json.dumps(record[key])}")
            record["scopes"] = _traced_scopes(send)
            for scope, sec in sorted(record["scopes"].items(),
                                     key=lambda kv: -kv[1])[:8]:
                print(f"scope {name} {scope} self_ms={sec * 1e3:.3f}")
            print(f"device_ms {name} "
                  f"{sum(record['scopes'].values()) * 1e3:.3f}")
            statements.append((record, v, rows))
        for d in devices[:1]:
            print(f"memory device={d.id} stats={json.dumps(d.memory_stats())}")
        resident = sum(a.nbytes for _, a in session.cache.resident_arrays())
        print(f"resident bytes={resident}")
    finally:
        mysql.sock.close()
        my.shutdown()

    t0 = time.monotonic()
    frames = compare.frames(tables, compare.union_columns(
        [t["oracle"].COLUMNS for t in cell.templates]))
    for record, v, rows in statements:
        expected = v["oracle"].expected(frames)
        diff = compare.first_mismatch(rows, expected, v["oracle"].KEY)
        record["oracle_match"] = diff is None
        if diff is not None:
            failures.append(f"{record['statement']}: {diff}")
        # the configuration promises exact integer sums and `first_mismatch`
        # lets 1e-6 through: integers are also held to equality here
        inexact = [(g, e) for got, exp in zip(
            rows, expected.itertuples(index=False)) for g, e in zip(got, exp)
            if isinstance(e, numbers.Integral) and int(g) != int(e)]
        record["integers_equal"] = diff is None and not inexact
        if diff is None and inexact:
            failures.append(f"{record['statement']}: integer {inexact[0][0]}, "
                            f"reference has {inexact[0][1]}")
        print(f"statement {record['statement']} rows={len(expected)} "
              f"oracle_match={record['oracle_match']} "
              f"integers_equal={record['integers_equal']} "
              f"first_run_s={record['sends'][0]['seconds']} "
              f"sends={len(record['sends'])} "
              f"last_run_s={record['sends'][-1]['seconds']}")
    print(f"oracle seconds={time.monotonic() - t0:.1f}")
    for f in failures:
        print(f"FAILED {f}")
    return {"ok": not failures, "failures": failures, "device": device,
            "sf": sf, "seed": seed,
            "statements": [record for record, *_ in statements]}


def _versions() -> dict:
    import importlib.metadata as md

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            out[pkg] = "not installed"
    return out


def run(sf: float, chips: int, seed: int) -> dict:
    """Drive the served path once on whatever backend JAX has (main() only
    lets a TPU through; tier-1 calls this at SF0.01 on CPU). Returns
    {"ok", "failures", "device", "statements", ...}; prints as it goes."""
    import jax

    sys.path.insert(0, REPO)
    # tests/ is not a package: its modules import their siblings by bare name
    sys.path.insert(0, os.path.join(REPO, "tests"))
    import tpch_oracle
    from test_mysql_protocol import FullClient
    from tpch_queries import QUERIES

    from starrocks_tpu.runtime.http_service import SqlHttpServer
    from starrocks_tpu.runtime.mysql_service import MySQLServer
    from starrocks_tpu.runtime.serving import ServingTier
    from starrocks_tpu.runtime.session import Session
    from starrocks_tpu.storage.catalog import tpch_catalog

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"versions {json.dumps(_versions())}")
    print(f"device {json.dumps(device)} chips_used={chips} sf={sf:g} "
          f"seed={seed} compile_cache={jax.config.jax_compilation_cache_dir}")
    failures: list = []
    if chips > len(devices):
        failures.append(f"--chips {chips} but JAX has {len(devices)} devices")
        return {"ok": False, "failures": failures, "device": device}

    if chips > 1:
        failures += _check_collectives(chips, seed)

    t0 = time.monotonic()
    catalog = tpch_catalog(sf, seed)
    print(f"generated lineitem_rows="
          f"{catalog.get_table('lineitem').row_count} "
          f"seconds={time.monotonic() - t0:.1f}")

    session = Session(catalog, dist_shards=chips if chips > 1 else None)
    tier = ServingTier(session)
    my = MySQLServer(session, port=0, tier=tier).start()
    ht = SqlHttpServer(session, port=0, tier=tier).start()
    mysql = FullClient("127.0.0.1", my.port)
    mysql.sock.settimeout(1100)  # a cold SF10 statement compiles for minutes
    web = http.client.HTTPConnection("127.0.0.1", ht.port, timeout=1100)

    def send(door: str, sql: str) -> list:
        if door == "mysql":
            return mysql.query(sql)[1]
        web.request("POST", "/query", json.dumps({"sql": sql}),
                    {"Content-Type": "application/json"})
        resp = web.getresponse()
        body = resp.read()
        if resp.status != 200:
            raise RuntimeError(f"http {resp.status}: {body[:300]!r}")
        return [tuple(r) for r in json.loads(body)["rows"]]

    statements = []  # [(record, qid, key, rows of the last send)]
    seen: set = set()  # queries this tier has already compiled
    try:
        for door, qid, key in STATEMENTS:
            name = f"{door}:q{qid}"
            sends, rows = _send_until_warm(
                name, lambda: send(door, QUERIES[qid]), failures)
            if qid not in seen and not sends[0]["compiles"]:
                failures.append(f"{name}: first send compiled no program")
            seen.add(qid)
            record = {"statement": name, "sends": sends}
            done = _last_attempt_info("compactions")
            if done:
                # rows in, slots out and index method of each `compact` in
                # the program the last send ran
                record["compactions"] = done
                print(f"compactions {name} {json.dumps(done)}")
            # per aggregate scope: rows, groups, integer columns handed in
            # and summed, limbs made and the formulation of its one batch of
            # segment sums; per plan-node scope: column, dictionary length,
            # TRUE codes, runs and formulation of each predicate over a
            # dictionary column
            for info in ("segment_sums", "dict_predicates"):
                found = _last_attempt_info(info)
                if found:
                    record[info] = found
                    print(f"{info} {name} {json.dumps(found)}")
            programs = _last_attempt_info("programs")
            if programs:
                # on a mesh: each fragment program the last send ran
                record["programs"] = programs
                for module, holds in programs.items():
                    print(f"program {name} name={module} compactions="
                          f"{json.dumps(holds['compactions'])} exchanges="
                          f"{json.dumps(holds['exchanges'])}")
                # Q3's `_f2` searches and groups lineitem: its probe side
                # must be down to its live rows first (`shrink_<n>l`, with
                # `live`, the fullest shard's rows, beside `cap`/`out_cap`)
                if qid == 3 and not _probe_shrinks(programs, 2):
                    failures.append(f"{name}: fragment _f2 compacts no "
                                    "probe side (no shrink_<n>l)")
            statements.append((record, qid, key, rows))

        # what the statements left on the device, before anything is freed
        resident = session.cache.resident_arrays()
        on = {d for _, a in resident for d in a.devices()}
        off = sorted({str(k[:3]) for k, a in resident
                      if any(d.platform != device["platform"]
                             for d in a.devices())})
        if off:
            failures.append(f"cached columns not on {device['platform']} "
                            f"devices: {off[:5]}")
        want = {(t, c) for t, cols in QUERY_COLUMNS.items() for c in cols}
        missing = sorted(want - {(k[0], k[1]) for k, _ in resident})
        if missing:
            failures.append(f"query columns not device-resident: {missing}")
        resident_bytes = sum(a.nbytes for _, a in resident)
        print(f"resident arrays={len(resident)} bytes={resident_bytes} "
              f"devices={sorted(d.id for d in on)}")
        if chips > 1:
            li = [a for k, a in resident
                  if (k[0], k[1]) == ("lineitem", "l_extendedprice")]
            if not li or any(len(a.devices()) != chips for a in li):
                failures.append(
                    f"lineitem.l_extendedprice does not span {chips} "
                    f"devices: {[len(a.devices()) for a in li]}")
        peaks = []
        for d in devices[:chips]:
            stats = d.memory_stats()
            peak = (stats or {}).get("peak_bytes_in_use")
            peaks.append(peak)
            print(f"memory device={d.id} peak_bytes_in_use={peak} "
                  f"stats={json.dumps(stats)}")
            if peak is None:
                # the CPU backend keeps no statistics; a TPU must
                if d.platform == "tpu":
                    failures.append(f"device {d.id}: no peak_bytes_in_use")
            elif peak < resident_bytes // chips:
                failures.append(
                    f"device {d.id}: peak_bytes_in_use {peak} is below its "
                    f"share of the {resident_bytes} resident bytes")
    finally:
        mysql.sock.close()
        web.close()
        my.shutdown()
        ht.stop()

    # oracles, outside anything timed above
    t0 = time.monotonic()
    frames = _oracle_frames(catalog)
    expected: dict = {}  # qid -> oracle rows (Q1 is asked through two doors)
    for st, qid, key, rows in statements:
        if qid not in expected:
            expected[qid] = [tuple(r) for r in getattr(
                tpch_oracle, f"q{qid}")(frames).itertuples(index=False)]
        exp = expected[qid]
        diff = _first_mismatch(rows, exp, key)
        st["oracle_match"] = diff is None
        if diff is not None:
            failures.append(f"{st['statement']}: {diff}")
        first, last = st["sends"][0], st["sends"][-1]
        print(f"statement {st['statement']} rows={len(exp)} "
              f"oracle_match={st['oracle_match']} "
              f"first_run_s={first['seconds']} "
              f"first_run_compiles={first['compiles']}"
              f"+{first['recompiles']}re "
              f"sends={len(st['sends'])} last_run_s={last['seconds']} "
              f"last_run_compiles={last['compiles']}")
    print(f"oracle seconds={time.monotonic() - t0:.1f}")

    for f in failures:
        print(f"FAILED {f}")
    return {"ok": not failures, "failures": failures, "device": device,
            "sf": sf, "seed": seed, "chips": chips,
            "statements": [st for st, *_ in statements],
            "resident_bytes": resident_bytes, "peak_bytes_in_use": peaks}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = the same statements through "
                         "Session(dist_shards=4) on a four-chip host")
    ap.add_argument("--suite", default="tpch", choices=("tpch", "ssb_flat"),
                    help="ssb_flat = upstream's 13 flat-table statements "
                         "over one chip's share of lineorder_flat")
    ap.add_argument("--sf", type=float, default=None,
                    help="scale factor (the deployments: TPC-H SF10, "
                         "SSB-flat SF100's share)")
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args()

    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's default backend is "
              f"{backend!r} (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS', '<unset>')})",
              file=sys.stderr)
        return 1
    # a hung chip becomes a traceback and a non-zero exit inside the limit
    faulthandler.dump_traceback_later(1170, exit=True)
    if args.suite == "ssb_flat":
        res = run_ssb_flat(100.0 if args.sf is None else args.sf, args.seed)
    else:
        res = run(10.0 if args.sf is None else args.sf, args.chips,
                  args.seed)
    if not res["ok"]:
        return 1
    print(json.dumps({"ok": True, "device": res["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
