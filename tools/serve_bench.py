#!/usr/bin/env python
"""Sustained mixed-workload serving benchmark (ROADMAP item 2).

Drives the serving tier (runtime/serving.py) the way a dashboard fleet
does: N client threads over REAL MySQL-wire and HTTP connections, firing
a Zipfian-weighted mix of TPC-H(+SSB-flat) statements against one shared
tier, and reports client-observed latency percentiles, sustained QPS,
admission/pool queue wait, and cache-hit rates — the first concurrency
numbers in the bench trajectory.

Phases:
  1. **setup/warmup** — build the in-memory TPC-H (and optionally SSB
     flat) catalog, start one MySQL + one HTTP front door over a shared
     ServingTier, run every template once so trace+compile costs are paid
     up front (the engine compiles per distinct plan; a serving mix keys
     the same programs afterwards).
  2. **cold** — `enable_query_cache=off`: every statement executes for
     real (planning + device dispatch) through the priority pool. Run
     twice: pool=1 (forced single-thread serialization — the pre-round-12
     behavior) and pool=N, same duration; their QPS ratio is the
     concurrency speedup on THIS box.
  3. **warm** — `enable_query_cache=on`: statements repeat Zipfian-hot,
     so most answers ride the plan-cache + result-cache inline fast path;
     reports warm p50/p99 and fast-path/cache hit rates.
  4. optional **--chaos** — arms a handful of failpoints (times-bounded)
     mid-run; the run must finish with zero leaked slots/bytes/registry
     entries and an acyclic lock-witness graph.
  5. **feedback** — in-process A/B of the plan-feedback loop (ISSUE 11):
     learn/repeat/steady passes with `plan_feedback` off vs on; the on
     arm must pre-tighten the restart-analog repeat pass to zero
     adaptive recompiles and hold steady-state fresh compiles at zero.
  6. **obs** — observability-plane overhead A/B (audit log +
     metrics-history sampler on vs off, interleaved rounds): warm
     fast-path p50 and point-lane p50 must regress <5% with the
     defaults ON (`--obs` runs just this phase; `--no-obs` skips it).
  7. **--ingest** — continuous-ingest phase: sustained HTTP stream-load
     lanes into one PK table under live analytic + point serving of a
     DIFFERENT table, reporting ingest_rows_s, staged->visible
     freshness p50, serving p99 under ingest vs baseline, and the idle
     cost of the enabled-but-unused plane.

Summary JSON prints on the last line (the driver's bench contract);
--detail merges a "serve" section into BENCH_DETAIL.json.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import random
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

# The backend is whatever JAX_PLATFORMS in the environment selects (a TPU
# where there is one; tools/run_tier1.sh passes JAX_PLATFORMS=cpu): no code
# here names a platform. Every summary carries the platform it ran on.


# --- query mix ----------------------------------------------------------------

# parameterized dashboard-style templates; each (template, param) combo is
# one distinct statement text. Plans key compiled programs by literal
# values, so the warmup pays one compile per combo — keep the cross
# product modest and the Zipf head hot.
TPCH_TEMPLATES = [
    ("returns_by_flag",
     "select l_returnflag, l_linestatus, count(*), sum(l_quantity) "
     "from lineitem where l_shipdate <= date '{d}' "
     "group by l_returnflag, l_linestatus order by l_returnflag, "
     "l_linestatus",
     [{"d": d} for d in ("1998-09-02", "1998-06-30", "1998-03-31")]),
    ("revenue_window",
     "select sum(l_extendedprice * l_discount) from lineitem "
     "where l_discount between {lo} and {hi} and l_quantity < {q}",
     [{"lo": 0.05, "hi": 0.07, "q": 24},
      {"lo": 0.03, "hi": 0.05, "q": 25},
      {"lo": 0.06, "hi": 0.08, "q": 24}]),
    ("orders_by_priority",
     "select o_orderpriority, count(*) from orders "
     "where o_orderdate >= date '{d}' group by o_orderpriority "
     "order by o_orderpriority",
     [{"d": d} for d in ("1995-01-01", "1996-01-01", "1997-01-01")]),
    ("top_customers",
     "select c_name, sum(o_totalprice) as spend from customer "
     "join orders on c_custkey = o_custkey group by c_name "
     "order by spend desc limit {k}",
     [{"k": 10}, {"k": 20}]),
    ("nation_mix",
     "select n_name, count(*) from customer "
     "join nation on c_nationkey = n_nationkey group by n_name "
     "order by n_name",
     [{}]),
]

SSB_TEMPLATES = [
    ("ssb_q11",
     "select sum(lo_extendedprice * lo_discount) as revenue "
     "from lineorder_flat where lo_discount between {lo} and {hi} "
     "and lo_quantity < {q}",
     [{"lo": 1, "hi": 3, "q": 25}, {"lo": 4, "hi": 6, "q": 35}]),
]


def build_statements(include_ssb: bool) -> list:
    out = []
    for name, tpl, params in TPCH_TEMPLATES:
        for i, p in enumerate(params):
            out.append((f"{name}#{i}", tpl.format(**p)))
    if include_ssb:
        for name, tpl, params in SSB_TEMPLATES:
            for i, p in enumerate(params):
                out.append((f"{name}#{i}", tpl.format(**p)))
    return out


def zipf_weights(n: int, s: float = 1.1) -> list:
    w = [1.0 / (i + 1) ** s for i in range(n)]
    total = sum(w)
    return [x / total for x in w]


# --- clients ------------------------------------------------------------------


class HttpClient:
    """Keep-alive HTTP /query client (one per thread)."""

    def __init__(self, port: int):
        self.conn = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=120)

    def query(self, sql: str):
        body = json.dumps({"sql": sql})
        self.conn.request("POST", "/query", body,
                          {"Content-Type": "application/json"})
        resp = self.conn.getresponse()
        data = resp.read()
        if resp.status != 200:
            raise RuntimeError(f"http {resp.status}: {data[:200]!r}")
        return json.loads(data)

    def close(self):
        self.conn.close()


def _drain_metrics():
    from starrocks_tpu.cache.query_cache import QCACHE_HITS
    from starrocks_tpu.runtime.serving import (
        SERVE_FAST_PATH, SERVE_QUEUE_WAIT_MS, SERVE_STATEMENTS)
    from starrocks_tpu.runtime.workgroup import (
        ADMISSION_ADMITTED, ADMISSION_QUEUE_WAIT_MS)

    return {
        "fast_path": SERVE_FAST_PATH.value,
        "statements": SERVE_STATEMENTS.value,
        "pool_wait_ms": SERVE_QUEUE_WAIT_MS.value,
        "qcache_hits": QCACHE_HITS.value,
        "admitted": ADMISSION_ADMITTED.value,
        "admission_wait_ms": ADMISSION_QUEUE_WAIT_MS.value,
    }


def run_phase(mysql_port: int, http_port: int, statements, weights,
              threads: int, seconds: float, http_frac: float,
              seed: int = 7) -> dict:
    """One timed phase: `threads` clients (a `http_frac` fraction over
    HTTP, the rest MySQL wire), each firing Zipfian-weighted statements
    until the deadline. Returns client-observed latency stats + metric
    deltas."""
    from test_mysql_protocol import MiniMySQLClient

    m0 = _drain_metrics()
    latencies: list = []
    errors: list = []
    lat_lock = threading.Lock()
    stop_at = [0.0]
    # two-phase start: (1) every client connected, (2) deadline armed —
    # the measured window must not start while connects are in flight
    barrier_conn = threading.Barrier(threads + 1)
    barrier_go = threading.Barrier(threads + 1)

    def client_loop(i: int):
        rng = random.Random(seed * 1000 + i)
        is_http = i < threads * http_frac
        cli = None
        try:
            time.sleep((i % 8) * 0.01)  # stagger the connect burst
            cli = (HttpClient(http_port) if is_http
                   else MiniMySQLClient("127.0.0.1", mysql_port))
        except Exception as e:  # noqa: BLE001
            errors.append(f"connect[{i}]: {e!r}")
        my: list = []
        barrier_conn.wait()
        barrier_go.wait()
        if cli is None:
            return
        while time.monotonic() < stop_at[0]:
            sql = rng.choices(statements, weights=weights, k=1)[0][1]
            t0 = time.perf_counter()
            try:
                cli.query(sql)
            except Exception as e:  # noqa: BLE001
                errors.append(f"{type(e).__name__}: {e}"[:200])
                continue
            my.append((time.perf_counter() - t0) * 1000.0)
        with lat_lock:
            latencies.extend(my)
        try:
            (cli.close if is_http else cli.quit)()
        except Exception:  # noqa: BLE001
            pass

    ts = [threading.Thread(target=client_loop, args=(i,), daemon=True)
          for i in range(threads)]
    for t in ts:
        t.start()
    barrier_conn.wait()  # every client finished connecting (or gave up)
    stop_at[0] = time.monotonic() + seconds
    t_start = time.monotonic()
    barrier_go.wait()    # clock armed: release the fleet
    for t in ts:
        t.join(timeout=seconds + 120)
    wall = time.monotonic() - t_start
    m1 = _drain_metrics()
    latencies.sort()

    def pct(p: float) -> float:
        if not latencies:
            return 0.0
        return latencies[min(int(len(latencies) * p), len(latencies) - 1)]

    n = len(latencies)
    stmts = max(m1["statements"] - m0["statements"], 1)
    return {
        "requests": n,
        "wall_s": round(wall, 2),
        "qps": round(n / wall, 1) if wall > 0 else 0.0,
        "p50_ms": round(pct(0.50), 3),
        "p95_ms": round(pct(0.95), 3),
        "p99_ms": round(pct(0.99), 3),
        "queue_wait_ms": round(
            (m1["pool_wait_ms"] - m0["pool_wait_ms"]
             + m1["admission_wait_ms"] - m0["admission_wait_ms"])
            / stmts, 3),
        "fast_path_rate": round(
            (m1["fast_path"] - m0["fast_path"]) / stmts, 3),
        "cache_hit_rate": round(
            (m1["qcache_hits"] - m0["qcache_hits"]) / stmts, 3),
        "errors": len(errors),
        "error_sample": errors[:3],
    }


def _pct(sorted_ms: list, p: float) -> float:
    if not sorted_ms:
        return 0.0
    return sorted_ms[min(int(len(sorted_ms) * p), len(sorted_ms) - 1)]


def _run_mixed_lane_phase(s, nrows: int, seconds: float) -> dict:
    """Mixed serving: analytic scans + point lookups + a per-second DML
    pulse against ONE tier over the SAME store-backed table, reporting
    per-lane latency. The per-table statement gate is what keeps the
    point lane inline here; the analytic lane and the DML pulse
    serialize against each other exactly as the correctness contract
    demands."""
    from starrocks_tpu.runtime.serving import ServingTier

    tier = ServingTier(s, pool_size=2)
    try:
        warm = tier.new_session()
        aq = "select count(*) c, sum(n) s from point_kv where n >= 0"
        tier.execute(warm, aq)  # pay the analytic compile up front
        buckets: dict = {"point": [], "analytic": [], "dml": []}
        lock = threading.Lock()
        stop_at = time.monotonic() + seconds

        def loop(lane: str, mk):
            sess = tier.new_session()
            my: list = []
            while time.monotonic() < stop_at:
                sql = mk()
                t0 = time.perf_counter()
                try:
                    tier.execute(sess, sql)
                except Exception:  # noqa: BLE001
                    continue
                my.append((time.perf_counter() - t0) * 1000.0)
                if lane == "dml":
                    time.sleep(0.5)  # per-second DML pulse, not a flood
            with lock:
                buckets[lane].extend(my)

        rp1, rp2, rd = (random.Random(101), random.Random(102),
                        random.Random(103))
        ts = [
            threading.Thread(target=loop, args=("analytic", lambda: aq),
                             daemon=True),
            threading.Thread(target=loop, args=(
                "point", lambda: "select v, n from point_kv where k = "
                f"{rp1.randrange(nrows)}"), daemon=True),
            threading.Thread(target=loop, args=(
                "point", lambda: "select v, n from point_kv where k = "
                f"{rp2.randrange(nrows)}"), daemon=True),
            threading.Thread(target=loop, args=(
                "dml", lambda: f"update point_kv set n = "
                f"{rd.randrange(10 ** 6)} where k = {rd.randrange(nrows)}"),
                daemon=True),
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=seconds + 120)
        out: dict = {}
        for lane, lat in buckets.items():
            lat.sort()
            out[f"{lane}_requests"] = len(lat)
            if lat:
                out[f"{lane}_p50_ms"] = round(_pct(lat, 0.50), 3)
                out[f"{lane}_p99_ms"] = round(_pct(lat, 0.99), 3)
        return out
    finally:
        tier.shutdown()


def run_point_phase(seconds: float = 4.0, nrows: int = 20000,
                    mixed: bool = True) -> dict:
    """Short-circuit point-query lane benchmark (the wire-speed PK-lookup
    plane). tpch_catalog is in-memory, so this phase builds its own
    TabletStore-backed PK table — the lane only exists over the stored
    primary index. Reports sustained in-proc point QPS/percentiles, the
    cold-analytic anchor for the same statement (lane off, fresh plans),
    and mixed-workload per-lane latency under a per-second DML pulse."""
    import shutil
    import tempfile

    from starrocks_tpu.cache import plan_cache  # noqa: F401 — knob define
    from starrocks_tpu.runtime.config import config
    from starrocks_tpu.runtime.session import Session

    d = tempfile.mkdtemp(prefix="sr_pointbench_")
    out: dict = {"rows": nrows}
    prev_plan = config.get("enable_plan_cache")
    try:
        s = Session(data_dir=os.path.join(d, "db"))
        s.sql("create table point_kv (k bigint, v varchar, n bigint, "
              "primary key(k))")
        for base in range(0, nrows, 2000):
            rows = ",".join(f"({i}, 'v{i}', {i * 7})"
                            for i in range(base, min(base + 2000, nrows)))
            s.sql(f"insert into point_kv values {rows}")
        rng = random.Random(11)

        # cold analytic anchor: the SAME statement with the lane off and
        # plan caching off — what every lookup would cost through the
        # full planner/compiler path
        config.set("enable_short_circuit", False)
        config.set("enable_plan_cache", False)
        lat: list = []
        for _ in range(12):
            k = rng.randrange(nrows)
            t0 = time.perf_counter()
            s.sql(f"select v, n from point_kv where k = {k}")
            lat.append((time.perf_counter() - t0) * 1000.0)
        lat.sort()
        out["analytic_cold_p50_ms"] = round(_pct(lat, 0.50), 3)
        config.set("enable_plan_cache", prev_plan)
        config.set("enable_short_circuit", True)

        # sustained in-proc point loop (single client; the wire adds its
        # own per-protocol cost on top of the engine answer path)
        lat = []
        deadline = time.monotonic() + seconds
        t_all = time.monotonic()
        while time.monotonic() < deadline:
            k = rng.randrange(int(nrows * 1.02))  # ~2% misses in the mix
            t0 = time.perf_counter()
            s.sql(f"select v, n from point_kv where k = {k}")
            lat.append((time.perf_counter() - t0) * 1000.0)
        wall = time.monotonic() - t_all
        lat.sort()
        out.update({
            "point_requests": len(lat),
            "point_qps": round(len(lat) / wall, 1) if wall else 0.0,
            "point_p50_ms": round(_pct(lat, 0.50), 3),
            "point_p99_ms": round(_pct(lat, 0.99), 3),
        })
        if out["point_p50_ms"]:
            out["point_vs_analytic_cold"] = round(
                out["analytic_cold_p50_ms"] / out["point_p50_ms"], 1)

        if mixed:
            out["mixed"] = _run_mixed_lane_phase(s, nrows, seconds)
    finally:
        config.set("enable_plan_cache", prev_plan)
        config.set("enable_short_circuit", True)
        shutil.rmtree(d, ignore_errors=True)
    return out


def run_feedback_phase(cat, statements) -> dict:
    """A/B of the plan-feedback loop (ISSUE 11) over the serve mix plus a
    guaranteed-overflow expansion join. Three passes per arm, in process:

      learn  — fresh session, cold everything: pays compiles AND the
               adaptive overflow retries that teach the store;
      repeat — NEW session (cold program/opt caches, the restart analog)
               with the feedback store carried over: feedback-on must
               pre-tighten to ZERO adaptive recompiles;
      steady — same session again: second executions must ride the
               program cache end to end (zero fresh compiles — the
               consult-token fixpoint keeping the opt-plan key warm).
    """
    import numpy as np

    from starrocks_tpu.column import HostTable
    from starrocks_tpu.runtime.config import config
    from starrocks_tpu.runtime.feedback import (
        FEEDBACK_EST_ERRSUM, FEEDBACK_EST_JOINS, FEEDBACK_HITS,
        FEEDBACK_RETRIES_AVOIDED)
    from starrocks_tpu.runtime.metrics import PROGRAM_COMPILES, RECOMPILES
    from starrocks_tpu.runtime.session import Session

    rng = np.random.default_rng(29)
    cat.register("fb_fact", HostTable.from_pydict({
        "k": [int(x) for x in rng.integers(0, 20, 2000)],
        "v": list(range(2000))}))
    cat.register("fb_dim", HostTable.from_pydict({
        "k": [int(x) for x in rng.integers(0, 20, 2000)],
        "w": list(range(2000))}))
    mix = [sql for _, sql in statements] + [
        "select count(*) c, sum(f.v + d.w) s from fb_fact f "
        "join fb_dim d on f.k = d.k"]

    def run_pass(sess) -> dict:
        c0, r0 = PROGRAM_COMPILES.value, RECOMPILES.value
        for sql in mix:
            sess.sql(sql)
        return {"compiles": PROGRAM_COMPILES.value - c0,
                "recompiles": RECOMPILES.value - r0}

    out: dict = {"mix_statements": len(mix)}
    try:
        for mode in ("off", "on"):
            config.set("plan_feedback", mode == "on")
            h0, a0 = FEEDBACK_HITS.value, FEEDBACK_RETRIES_AVOIDED.value
            e0, j0 = FEEDBACK_EST_ERRSUM.value, FEEDBACK_EST_JOINS.value
            s1 = Session(cat)
            res = {"learn": run_pass(s1)}
            s2 = Session(cat)  # restart analog: cold caches, same catalog
            s2.cache.feedback = s1.cache.feedback
            res["repeat"] = run_pass(s2)
            res["steady"] = run_pass(s2)
            res["feedback_hits"] = FEEDBACK_HITS.value - h0
            res["retries_avoided"] = FEEDBACK_RETRIES_AVOIDED.value - a0
            joins = FEEDBACK_EST_JOINS.value - j0
            if joins:
                res["est_rel_err"] = round(
                    (FEEDBACK_EST_ERRSUM.value - e0) / joins, 3)
            out[mode] = res
    finally:
        config.set("plan_feedback", True)
        cat.drop("fb_fact", if_exists=True)
        cat.drop("fb_dim", if_exists=True)
    out["repeat_retries_saved_vs_off"] = (
        out["off"]["repeat"]["recompiles"]
        - out["on"]["repeat"]["recompiles"])
    return out


def run_obs_phase(iters: int = 240, nrows: int = 8000) -> dict:
    """Observability-plane overhead A/B: the WHOLE derived plane ON (the
    shipped defaults — audit log, metrics-history sampler + alert rules,
    workload aggregator, plan sentinel, stuck-query watchdog) vs OFF,
    over the two latencies the plane must NOT tax — the warm in-proc
    fast path (result-cache inline answer) and the point lane
    (planner-free PK lookup). The event journal has no off switch, but
    none of its sites fire on either lane, so the toggled set IS the
    per-statement delta. Arms alternate in interleaved rounds so host
    drift cancels out of the comparison; acceptance is <5% p50
    regression on both lanes (obs work rides the unwind hook and
    background threads, never the answer path)."""
    import shutil
    import tempfile

    from starrocks_tpu.runtime import audit  # noqa: F401 — knob define
    from starrocks_tpu.runtime.alerts import ALERTS
    from starrocks_tpu.runtime.config import config
    from starrocks_tpu.runtime.metrics import HISTORY
    from starrocks_tpu.runtime.sentinel import SENTINEL
    from starrocks_tpu.runtime.session import Session
    from starrocks_tpu.runtime.watchdog import WATCHDOG
    from starrocks_tpu.runtime.workload import WORKLOAD

    d = tempfile.mkdtemp(prefix="sr_obsbench_")
    # every knob the A/B toggles (the round-19 derived plane included)
    _ARM_KNOBS = ("enable_audit_log", "enable_metrics_history",
                  "enable_alerts", "enable_workload_stats",
                  "enable_plan_sentinel", "enable_watchdog")
    prev = {k: config.get(k) for k in _ARM_KNOBS}
    prev_qc = config.get("enable_query_cache")
    out: dict = {}
    try:
        s = Session(data_dir=os.path.join(d, "db"))
        s.sql("create table obs_kv (k bigint, v varchar, n bigint, "
              "primary key(k))")
        for base in range(0, nrows, 2000):
            rows = ",".join(f"({i}, 'v{i}', {i * 3})"
                            for i in range(base, min(base + 2000, nrows)))
            s.sql(f"insert into obs_kv values {rows}")
        config.set("enable_query_cache", True)
        warm_sql = "select count(*) c, sum(n) sn from obs_kv"
        rng = random.Random(7)

        def one_warm():
            s.sql(warm_sql)

        def one_point():
            s.sql(f"select v, n from obs_kv where k = {rng.randrange(nrows)}")

        def set_arm(on: bool):
            for k in _ARM_KNOBS:
                config.set(k, on)
            if on:
                HISTORY.ensure_started()
                WATCHDOG.ensure_started()
            else:
                HISTORY.stop()
                WATCHDOG.stop()

        for _ in range(20):  # shared warmup: pay compiles, prime caches
            one_warm()
            one_point()
        lats: dict = {(lane, on): []
                      for lane in ("warm", "point") for on in (True, False)}
        rounds = 8
        per = max(iters // rounds, 10)
        for r in range(rounds):
            for on in ((True, False) if r % 2 == 0 else (False, True)):
                set_arm(on)
                for _ in range(3):  # settle the arm switch
                    one_warm()
                    one_point()
                for lane, fn in (("warm", one_warm), ("point", one_point)):
                    for _ in range(per):
                        t0 = time.perf_counter()
                        fn()
                        lats[(lane, on)].append(
                            (time.perf_counter() - t0) * 1000)

        def p50(lane, on):
            v = sorted(lats[(lane, on)])
            return v[len(v) // 2]

        out["obs_on_warm_p50_ms"] = round(p50("warm", True), 3)
        out["obs_off_warm_p50_ms"] = round(p50("warm", False), 3)
        out["obs_on_point_p50_ms"] = round(p50("point", True), 3)
        out["obs_off_point_p50_ms"] = round(p50("point", False), 3)
        warm_reg = p50("warm", True) / max(p50("warm", False), 1e-9) - 1
        point_reg = p50("point", True) / max(p50("point", False), 1e-9) - 1
        out["obs_warm_regress_pct"] = round(warm_reg * 100, 1)
        out["obs_point_regress_pct"] = round(point_reg * 100, 1)
        out["obs_pass"] = bool(warm_reg < 0.05 and point_reg < 0.05)
        # derived-plane bookkeeping after the sustained run: the summary
        # JSON records that the new state stayed hard-bounded while every
        # statement of the bench flowed through it
        wst = WORKLOAD.stats()
        ast_ = ALERTS.stats()
        out["workload_entries"] = wst["entries"]
        out["workload_registered"] = wst["registered"]
        out["workload_evicted"] = wst["evicted"]
        out["alert_rules"] = ast_["rules"]
        out["alert_firing"] = ast_["firing"]
        out["alert_fires"] = ast_["fires"]
        out["sentinel_entries"] = SENTINEL.stats()["entries"]
    finally:
        for k, v in prev.items():
            config.set(k, v)
        config.set("enable_query_cache", prev_qc)
        shutil.rmtree(d, ignore_errors=True)
    return out


def run_ingest_phase(seconds: float = 6.0, nrows: int = 12000,
                     loaders: int = 1, put_rows: int = 1000) -> dict:
    """Continuous-ingest phase: sustained HTTP stream-load lanes into one
    PK table while a Zipfian analytic lane and the point lane keep
    serving a DIFFERENT table through the same tier — the plan-footprint
    gate claims are what keep the serving lanes out of the ingest
    commits' way. Reports sustained ingest rows/s, staged->visible
    freshness p50 (the sr_tpu_ingest_freshness_ms histogram), serving
    latency under ingest vs a no-ingest baseline on the SAME process,
    and the idle cost of merely having the plane enabled (A/B toggling
    `enable_ingest_plane` with zero load traffic)."""
    import shutil
    import tempfile

    from starrocks_tpu.ingest.plane import INGEST_FRESHNESS_MS
    from starrocks_tpu.runtime.config import config
    from starrocks_tpu.runtime.http_service import SqlHttpServer
    from starrocks_tpu.runtime.serving import ServingTier
    from starrocks_tpu.runtime.session import Session

    d = tempfile.mkdtemp(prefix="sr_ingestbench_")
    prev_qc = config.get("enable_query_cache")
    out: dict = {"loaders": loaders, "put_rows": put_rows}
    half = max(seconds / 2.0, 2.0)
    try:
        s = Session(data_dir=os.path.join(d, "db"))
        s.sql("create table serve_kv (k bigint, v varchar, n bigint, "
              "primary key(k))")
        for base in range(0, nrows, 2000):
            rows = ",".join(f"({i}, 'v{i}', {i * 3})"
                            for i in range(base, min(base + 2000, nrows)))
            s.sql(f"insert into serve_kv values {rows}")
        s.sql("create table ingest_sink (k bigint, v bigint, "
              "primary key(k))")
        tier = ServingTier(s, pool_size=2)
        plane = s.ingest_plane()  # wires the tier's gate into commits
        ht = SqlHttpServer(s, port=0, tier=tier).start()
        config.set("enable_query_cache", False)
        # freshness-oriented commit policy for the sustained window: a
        # stream-load fleet tunes the age bound down exactly like this
        config.set("ingest_batch_age_ms", 50)
        analytic = [
            "select count(*) c, sum(n) sn from serve_kv where n >= 0",
            "select count(*) c, max(n) mn from serve_kv where k < "
            f"{nrows // 2}",
            "select min(k) a, max(k) b from serve_kv where n % 2 = 0",
        ]
        aw = zipf_weights(len(analytic))
        sess = tier.new_session()
        for sql in analytic:  # pay compiles before any timed window
            tier.execute(sess, sql)

        rng_idle = random.Random(13)

        def point_once(sess_, rng):
            tier.execute(sess_, "select v, n from serve_kv where k = "
                         f"{rng.randrange(nrows)}")

        # --- idle A/B: the enabled-but-unused plane must cost ~nothing
        def idle_p50(iters=150):
            lat = []
            for _ in range(iters):
                t0 = time.perf_counter()
                point_once(sess, rng_idle)
                lat.append((time.perf_counter() - t0) * 1000)
            lat.sort()
            return lat[len(lat) // 2]

        idle_p50(30)  # warm the lane before either arm samples
        config.set("enable_ingest_plane", False)
        p_off = idle_p50()
        config.set("enable_ingest_plane", True)
        p_on = idle_p50()
        out["idle_point_p50_plane_off_ms"] = round(p_off, 3)
        out["idle_point_p50_plane_on_ms"] = round(p_on, 3)
        out["idle_regress_pct"] = round((p_on / max(p_off, 1e-9) - 1)
                                        * 100, 1)

        # --- serving lanes (shared by baseline and under-ingest windows)
        def lanes(duration: float) -> dict:
            buckets = {"point": [], "analytic": []}
            lock = threading.Lock()
            stop_at = time.monotonic() + duration

            def loop(lane, fn):
                sess_ = tier.new_session()
                rng = random.Random(hash(lane) & 0xFFFF)
                my = []
                while time.monotonic() < stop_at:
                    t0 = time.perf_counter()
                    try:
                        fn(sess_, rng)
                    except Exception:  # noqa: BLE001
                        continue
                    my.append((time.perf_counter() - t0) * 1000)
                with lock:
                    buckets[lane].extend(my)

            def analytic_once(sess_, rng):
                tier.execute(
                    sess_, rng.choices(analytic, weights=aw, k=1)[0])

            ts = [threading.Thread(target=loop, args=("point", point_once),
                                   daemon=True),
                  threading.Thread(target=loop,
                                   args=("analytic", analytic_once),
                                   daemon=True)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=duration + 120)
            res = {}
            for lane, lat in buckets.items():
                lat.sort()
                res[f"{lane}_requests"] = len(lat)
                res[f"{lane}_p50_ms"] = round(_pct(lat, 0.50), 3)
                res[f"{lane}_p99_ms"] = round(_pct(lat, 0.99), 3)
            return res

        base = lanes(half)
        out["baseline"] = base

        # --- sustained stream load over HTTP + the same serving lanes
        rows_acked = [0] * loaders
        errors: list = []
        stop_at = [time.monotonic() + half]

        def loader(i: int):
            conn = http.client.HTTPConnection("127.0.0.1", ht.port,
                                              timeout=120)
            seq = 0
            while time.monotonic() < stop_at[0]:
                base_k = (i << 40) + seq * put_rows
                body = "\n".join(f"{base_k + j},{j}"
                                 for j in range(put_rows))
                try:
                    conn.request("PUT", "/api/load/ingest_sink", body)
                    resp = conn.getresponse()
                    data = resp.read()
                    if resp.status == 429:
                        time.sleep(0.05)  # backpressure: retry later
                        continue
                    if resp.status != 200:
                        errors.append(f"{resp.status}: {data[:120]!r}")
                        continue
                    rows_acked[i] += json.loads(data)["rows"]
                    seq += 1
                except Exception as e:  # noqa: BLE001
                    errors.append(repr(e)[:120])
            conn.close()

        f0_counts, _f0_sum, f0_n = INGEST_FRESHNESS_MS.snapshot()
        ts = [threading.Thread(target=loader, args=(i,), daemon=True)
              for i in range(loaders)]
        t0 = time.monotonic()
        for t in ts:
            t.start()
        under = lanes(half)
        for t in ts:
            t.join(timeout=half + 120)
        wall = time.monotonic() - t0
        out["under_ingest"] = under
        out["ingest_rows"] = sum(rows_acked)
        out["ingest_rows_s"] = round(sum(rows_acked) / wall, 1)
        out["ingest_errors"] = len(errors)
        out["ingest_error_sample"] = errors[:3]
        # freshness over THIS window: subtract the pre-window histogram
        f1_counts, _f1_sum, f1_n = INGEST_FRESHNESS_MS.snapshot()
        out["ingest_freshness_p50_ms"] = round(
            _hist_delta_percentile(INGEST_FRESHNESS_MS, f0_counts, f0_n,
                                   f1_counts, f1_n, 0.5), 1)
        out["point_p99_under_ingest_ms"] = under["point_p99_ms"]
        sink = s.sql("select count(*) from ingest_sink").rows()[0][0]
        out["ingest_rows_visible"] = int(sink)
        out["ingest_pass"] = bool(
            out["ingest_rows_s"] >= 5000
            and out["ingest_freshness_p50_ms"] < 1000
            and under["point_p99_ms"] < 2 * max(base["point_p99_ms"], 0.5)
            and sink == sum(rows_acked))
        ht.stop()
    finally:
        config.set("enable_query_cache", prev_qc)
        config.set("enable_ingest_plane", True)
        config.set("ingest_batch_age_ms", 200)
        shutil.rmtree(d, ignore_errors=True)
    return out


def _hist_delta_percentile(hist, c0, n0, c1, n1, q: float) -> float:
    """q-quantile of the observations a histogram gained between two
    snapshots (c0/n0 -> c1/n1), by the same interpolation its own
    percentile() uses — serve_bench windows need per-phase freshness,
    not process-lifetime freshness."""
    n = n1 - n0
    if n <= 0:
        return 0.0
    deltas = [a - b for a, b in zip(c1, c0)]
    rank = q * n
    seen = 0.0
    for i, cnt in enumerate(deltas):
        if cnt <= 0:
            continue
        if seen + cnt >= rank:
            lo = hist.buckets[i - 1] if i > 0 else 0.0
            hi = (hist.buckets[i] if i < len(hist.buckets)
                  else hist.buckets[-1])
            frac = (rank - seen) / cnt
            return lo + (hi - lo) * min(max(frac, 0.0), 1.0)
        seen += cnt
    return hist.buckets[-1]


def run_serve_bench(threads: int = 32, seconds: float = 8.0,
                    sf: float = 0.01, pool: int = 4,
                    include_ssb: bool = False, http_frac: float = 0.25,
                    chaos: bool = False, single_thread_ab: bool = True,
                    warm: bool = True, feedback: bool = True,
                    points: bool = True, obs: bool = True) -> dict:
    from starrocks_tpu import lockdep
    from starrocks_tpu.runtime import failpoint
    from starrocks_tpu.runtime.config import config
    from starrocks_tpu.runtime.http_service import SqlHttpServer
    from starrocks_tpu.runtime.lifecycle import ACCOUNTANT, REGISTRY
    from starrocks_tpu.runtime.mysql_service import MySQLServer
    from starrocks_tpu.runtime.serving import ServingTier
    from starrocks_tpu.runtime.session import Session
    from starrocks_tpu.storage.catalog import tpch_catalog

    out_points = None
    if points:
        # runs FIRST so its store-backed table allocates before the leak
        # audit's baseline snapshot
        out_points = run_point_phase(seconds=min(seconds, 4.0))

    out_obs = None
    if obs:
        # also before the leak baseline: the A/B builds (and drops) its
        # own store-backed PK table
        out_obs = run_obs_phase()

    t_setup = time.monotonic()
    cat = tpch_catalog(sf=sf)
    if include_ssb:
        from starrocks_tpu.storage.datagen.ssb import ssb_catalog

        scat = ssb_catalog(sf=sf)
        # only the flat table: SSB's dimension tables share names with
        # TPC-H (customer/supplier/part) but carry different schemas
        cat.tables["lineorder_flat"] = scat.tables["lineorder_flat"]
    template = Session(cat)
    statements = build_statements(include_ssb)
    weights = zipf_weights(len(statements))

    out: dict = {
        "threads": threads, "seconds": seconds, "sf": sf, "pool": pool,
        "statements": len(statements), "mix": "zipf-1.1",
        "backend": _backend(),
        # pool speedup is bounded by host cores: on a 1-core box the A/B
        # signal is queue-wait collapse, not QPS (see BENCH_DETAIL notes)
        "host_cpus": os.cpu_count(),
    }
    config.set("enable_plan_cache", True)
    config.set("enable_query_cache", False)

    def fresh_tier(size: int):
        tier = ServingTier(template, pool_size=size)
        my = MySQLServer(template, port=0, tier=tier).start()
        ht = SqlHttpServer(template, port=0, tier=tier).start()
        return tier, my, ht

    tier, my, ht = fresh_tier(pool)
    try:
        # warmup: pay every trace+compile once (single client, in order)
        warm_sess = tier.new_session()
        for _, sql in statements:
            tier.execute(warm_sess, sql)
        out["setup_s"] = round(time.monotonic() - t_setup, 1)

        mem0 = ACCOUNTANT.snapshot()["process_bytes"]
        if chaos:
            # times-bounded faults land mid-run; the tier must shed them
            # cleanly (errors count, nothing leaks)
            for name in ("executor::fetch_results", "qcache::lookup",
                         "workgroup::admit"):
                failpoint.arm(name, times=3)
            out["chaos"] = True

        # cold phase (pool = N): real execution, concurrent
        out["cold"] = run_phase(my.port, ht.port, statements, weights,
                                threads, seconds, http_frac)
        if chaos:
            for name in ("executor::fetch_results", "qcache::lookup",
                         "workgroup::admit"):
                failpoint.disarm(name)
    finally:
        my.shutdown()
        ht.stop()

    if single_thread_ab:
        # forced single-thread run: pool=1 serializes every statement —
        # the pre-serving-tier behavior, same box, same warmed programs
        tier1, my1, ht1 = fresh_tier(1)
        try:
            out["cold_single"] = run_phase(
                my1.port, ht1.port, statements, weights, threads, seconds,
                http_frac)
        finally:
            my1.shutdown()
            ht1.stop()
        if out["cold_single"]["qps"]:
            out["speedup_vs_single"] = round(
                out["cold"]["qps"] / out["cold_single"]["qps"], 2)

    if warm:
        config.set("enable_query_cache", True)
        tier2, my2, ht2 = fresh_tier(pool)
        try:
            sess = tier2.new_session()
            for _, sql in statements:  # prime the result tier
                tier2.execute(sess, sql)
            out["warm"] = run_phase(my2.port, ht2.port, statements,
                                    weights, threads, seconds, http_frac)
            # in-process fast-path latency (no wire): the <1ms claim is
            # about the ENGINE answer path; sockets add their own cost
            hot_sql = statements[0][1]
            lat = []
            for _ in range(50):
                t0 = time.perf_counter()
                tier2.execute(sess, hot_sql)
                lat.append((time.perf_counter() - t0) * 1000)
            lat.sort()
            out["warm_inproc_p50_ms"] = round(lat[len(lat) // 2], 3)
        finally:
            my2.shutdown()
            ht2.stop()
            config.set("enable_query_cache", False)

    if feedback:
        out["feedback"] = run_feedback_phase(cat, statements)

    if out_points is not None:
        out["points"] = out_points
    if out_obs is not None:
        out["obs"] = out_obs

    # leak + witness audit (the chaos-suite contract, applied to serving)
    wm = getattr(cat, "workgroups", None)
    out["leaks"] = {
        "process_bytes": ACCOUNTANT.snapshot()["process_bytes"] - mem0,
        "registry": len(REGISTRY.snapshot()),
        "slots_running": (sum(wm.running.values()) if wm else 0),
    }
    out["witness_cycles"] = len(lockdep.WITNESS.order_cycles())
    return out


def run_cluster_phase(workers: int = 2, clients: int = 4,
                      seconds: float = 8.0) -> dict:
    """--cluster: N client threads against a coordinator + M worker
    PROCESSES (runtime/cluster_exec.py), two timed windows. The cluster
    runtime is a CPU-process plane (each worker is pinned to
    JAX_PLATFORMS=cpu; a chip belongs to one process), so run this phase
    with JAX_PLATFORMS=cpu: its figures are CPU figures.

      steady — every client fires fragment queries against the healthy
        fleet (each answer checked against a pre-cluster local oracle).
      kill   — same load; 25% into the window one worker is SIGKILL'd.
        Queries in flight across the kill re-place their fragments onto
        the survivors; the phase reports the worst straddling-query
        latency (retry latency) and the post-kill p99 — the acceptance
        gate is that the post-kill p99 is FINITE (no wedged query).

    Afterwards the dead worker is respawned and the fleet must report
    zero dead workers again (gauge recovery), with zero leaked slots/
    bytes/registry entries."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        # the coordinator session is distributed (dist_shards=2): widen
        # this process's host platform BEFORE any jax backend initializes
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=2").strip()
    import starrocks_tpu.sql.distributed as D
    from starrocks_tpu import lockdep
    from starrocks_tpu.runtime.cluster import WORKERS_DEAD
    from starrocks_tpu.runtime.cluster_exec import ClusterRuntime
    from starrocks_tpu.runtime.config import config
    from starrocks_tpu.runtime.events import EVENTS
    from starrocks_tpu.runtime.lifecycle import ACCOUNTANT, REGISTRY

    sh0, gr0 = D.SHARD_THRESHOLD_ROWS, D.SHUFFLE_AGG_MIN_GROUPS
    frag0 = config.get("dist_fragments")
    qc0 = config.get("enable_query_cache")
    D.SHARD_THRESHOLD_ROWS = 100
    D.SHUFFLE_AGG_MIN_GROUPS = 10
    config.set("dist_fragments", True)
    config.set("enable_query_cache", False)

    from starrocks_tpu.runtime.session import Session

    s = Session(dist_shards=2)
    s.sql("create table t (a int, b int)")
    s.sql("insert into t values "
          + ", ".join(f"({i % 97}, {i % 7})" for i in range(400)))
    s.sql("create table d (k int, v int)")
    s.sql("insert into d values "
          + ", ".join(f"({i}, {i * 10})" for i in range(97)))
    variants = [
        "select d.v, sum(t.b) s from t join d on t.a = d.k "
        f"group by d.v order by s desc, d.v limit {n}" for n in (5, 7, 9)
    ]
    oracles = {sql: s.sql(sql).rows() for sql in variants}

    t_setup = time.monotonic()
    cr = ClusterRuntime(n_workers=workers, shards=2, hb_interval_s=0.1,
                        hb_miss_limit=3).start(s)
    cr.attach(s)
    mem0 = ACCOUNTANT.snapshot()["process_bytes"]
    errors: list = []
    lat_lock = threading.Lock()

    def timed_window(window_s: float, kill_at_frac: float | None):
        """Run `clients` sessions over the shared catalog for window_s;
        optionally SIGKILL w0 at kill_at_frac of the window. Returns
        (samples, kill_ts) where samples are (t0, t1, ms) monotonic."""
        samples: list = []
        stop_at = time.monotonic() + window_s
        kill_ts = [None]

        def client_loop(i: int):
            rng = random.Random(4200 + i)
            cs = Session(catalog=s.catalog, cache=s.cache, dist_shards=2)
            my: list = []
            while time.monotonic() < stop_at:
                sql = rng.choice(variants)
                t0 = time.monotonic()
                try:
                    rows = cs.sql(sql).rows()
                except Exception as e:  # noqa: BLE001
                    errors.append(f"{type(e).__name__}: {e}"[:200])
                    continue
                t1 = time.monotonic()
                if rows != oracles[sql]:
                    errors.append(f"oracle mismatch on: {sql[-20:]}")
                my.append((t0, t1, (t1 - t0) * 1000.0))
            with lat_lock:
                samples.extend(my)

        threads_ = [threading.Thread(target=client_loop, args=(i,),
                                     daemon=True) for i in range(clients)]
        for th in threads_:
            th.start()
        if kill_at_frac is not None:
            time.sleep(window_s * kill_at_frac)
            # hold w0's next fragment in a delay so the SIGKILL lands
            # mid-fragment — the retry path, not just a re-placement of
            # future fragments onto the survivors
            cr.inject_fault("w0", "delay", seconds=2.0, times=1)
            time.sleep(0.6)  # let a fragment land in w0's delay window
            kill_ts[0] = time.monotonic()
            cr.kill_worker("w0")
        for th in threads_:
            th.join(timeout=window_s + 120.0)
        if any(th.is_alive() for th in threads_):
            errors.append("wedged client: a query never returned")
        return samples, kill_ts[0]

    out: dict = {"cluster_workers": workers, "cluster_clients": clients}
    try:
        for sql in variants:  # warm: fragment programs cached fleet-wide
            if s.sql(sql).rows() != oracles[sql]:
                errors.append("warm-up cluster answer diverged")
        out["setup_s"] = round(time.monotonic() - t_setup, 1)
        r0 = cr.stats()["retries_total"]
        loss0 = EVENTS.stats().get("heartbeat_loss", 0)

        steady, _ = timed_window(seconds / 2, None)
        sl = sorted(ms for _, _, ms in steady)
        out["steady"] = {
            "queries": len(sl), "qps": round(len(sl) / (seconds / 2), 1),
            "p50_ms": round(_pct(sl, 0.50), 2),
            "p99_ms": round(_pct(sl, 0.99), 2),
        }

        killed, kill_ts = timed_window(seconds / 2, 0.25)
        post = sorted(ms for _, t1, ms in killed if t1 >= kill_ts)
        straddle = [ms for t0, t1, ms in killed if t0 < kill_ts <= t1]
        out["kill"] = {
            "queries": len(killed), "post_kill": len(post),
            "straddling": len(straddle),
            "retry_latency_ms": round(max(straddle), 2) if straddle
            else None,
            "p99_ms": round(_pct(post, 0.99), 2),
        }
        out["cluster_retries"] = cr.stats()["retries_total"] - r0
        out["cluster_kill_p99_ms"] = out["kill"]["p99_ms"]
        if not post:
            errors.append("kill phase produced no post-kill samples")

        # recovery: the fleet heals and the observability plane saw it
        if EVENTS.stats().get("heartbeat_loss", 0) <= loss0:
            errors.append("kill was not observed (no heartbeat_loss)")
        cr.respawn_worker("w0")
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline and WORKERS_DEAD.value > 0:
            time.sleep(0.1)
        out["recovered"] = WORKERS_DEAD.value == 0
        if not out["recovered"]:
            errors.append("dead-worker gauge did not recover after "
                          "respawn")
    finally:
        s.catalog.cluster_runtime = None
        cr.stop()
        D.SHARD_THRESHOLD_ROWS, D.SHUFFLE_AGG_MIN_GROUPS = sh0, gr0
        config.set("dist_fragments", frag0)
        config.set("enable_query_cache", qc0)

    out["leaks"] = {
        "process_bytes": ACCOUNTANT.snapshot()["process_bytes"] - mem0,
        "registry": len(REGISTRY.snapshot()),
    }
    out["witness_cycles"] = len(lockdep.WITNESS.order_cycles())
    out["errors"] = errors[:5]
    out["cluster_pass"] = (
        not errors and out["cluster_kill_p99_ms"] > 0.0
        and not out["leaks"]["process_bytes"] and not out["leaks"]["registry"]
        and not out["witness_cycles"])
    return out


def _backend() -> str:
    import jax

    return jax.devices()[0].platform


def main():
    ap = argparse.ArgumentParser(
        description="sustained mixed-workload serving benchmark")
    ap.add_argument("--threads", type=int, default=32)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--sf", type=float, default=0.01)
    ap.add_argument("--pool", type=int, default=4)
    ap.add_argument("--ssb", action="store_true",
                    help="add SSB lineorder_flat templates to the mix")
    ap.add_argument("--http-frac", type=float, default=0.25,
                    help="fraction of clients on the HTTP front door")
    ap.add_argument("--chaos", action="store_true",
                    help="arm times-bounded failpoints mid-run")
    ap.add_argument("--no-ab", action="store_true",
                    help="skip the forced single-thread A/B run")
    ap.add_argument("--no-warm", action="store_true",
                    help="skip the warm (query-cache on) phase")
    ap.add_argument("--no-feedback", action="store_true",
                    help="skip the plan-feedback effectiveness A/B phase")
    ap.add_argument("--points", action="store_true",
                    help="run ONLY the short-circuit point-query phase")
    ap.add_argument("--no-points", action="store_true",
                    help="skip the point-query phase in the full run")
    ap.add_argument("--ingest", action="store_true",
                    help="run ONLY the continuous-ingest phase (stream "
                         "load + serving lanes; rows/s, freshness, "
                         "p99-under-ingest, idle-cost gates)")
    ap.add_argument("--obs", action="store_true",
                    help="run ONLY the observability-overhead A/B phase "
                         "(audit+events+sampler on vs off; <5%% gate)")
    ap.add_argument("--no-obs", action="store_true",
                    help="skip the observability A/B phase in the full run")
    ap.add_argument("--cluster", action="store_true",
                    help="run ONLY the cluster phase: clients against a "
                         "coordinator + worker PROCESSES with a "
                         "kill-one-worker window (retry latency + "
                         "post-kill p99); a CPU-process plane — run it "
                         "with JAX_PLATFORMS=cpu")
    ap.add_argument("--cluster-workers", type=int, default=2,
                    help="worker processes for --cluster")
    ap.add_argument("--cluster-clients", type=int, default=4,
                    help="client threads for --cluster")
    ap.add_argument("--detail", action="store_true",
                    help="merge a 'serve' section into BENCH_DETAIL.json")
    args = ap.parse_args()

    if args.cluster:
        res = run_cluster_phase(workers=args.cluster_workers,
                                clients=args.cluster_clients,
                                seconds=args.seconds)
        res["backend"] = _backend()
        if args.detail:
            path = os.path.join(REPO, "BENCH_DETAIL.json")
            detail = {}
            if os.path.exists(path):
                with open(path) as f:
                    detail = json.load(f)
            detail["cluster"] = res
            with open(path, "w") as f:
                json.dump(detail, f, indent=1)
        print(json.dumps(res))
        return 0 if res["cluster_pass"] else 1

    if args.points:
        res = {"points": run_point_phase(seconds=args.seconds),
               "backend": _backend()}
        print(json.dumps(res))
        return 0

    if args.obs:
        res = {"obs": run_obs_phase(), "backend": _backend()}
        print(json.dumps(res))
        return 0 if res["obs"]["obs_pass"] else 1

    if args.ingest:
        res = {"ingest": run_ingest_phase(seconds=args.seconds),
               "backend": _backend()}
        if args.detail:
            path = os.path.join(REPO, "BENCH_DETAIL.json")
            detail = {}
            if os.path.exists(path):
                with open(path) as f:
                    detail = json.load(f)
            detail["ingest"] = res["ingest"]
            with open(path, "w") as f:
                json.dump(detail, f, indent=1)
        print(json.dumps(res))
        return 0 if res["ingest"]["ingest_pass"] else 1

    res = run_serve_bench(
        threads=args.threads, seconds=args.seconds, sf=args.sf,
        pool=args.pool, include_ssb=args.ssb, http_frac=args.http_frac,
        chaos=args.chaos, single_thread_ab=not args.no_ab,
        warm=not args.no_warm, feedback=not args.no_feedback,
        points=not args.no_points, obs=not args.no_obs)
    if args.detail:
        path = os.path.join(REPO, "BENCH_DETAIL.json")
        detail = {}
        if os.path.exists(path):
            with open(path) as f:
                detail = json.load(f)
        detail["serve"] = res
        if "feedback" in res:
            detail["feedback"] = res["feedback"]
        with open(path, "w") as f:
            json.dump(detail, f, indent=1)
    print(json.dumps(res))
    leaks = res.get("leaks", {})
    obs_fail = "obs" in res and not res["obs"].get("obs_pass")
    bad = (res.get("witness_cycles", 0)
           or leaks.get("process_bytes") or leaks.get("slots_running")
           or obs_fail)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
