#!/usr/bin/env python
"""Join-engine microbench (round 13): same-box A/B of the three join
upgrades, merged into BENCH_DETAIL.json under "join_bench".

1. unique-join probe strategy: sorted (jnp argsort + searchsorted) vs
   pallas_sorted (explicit binary-search ladder kernel) vs pallas
   (open-addressing hash-table build+probe kernels). Off-TPU the Pallas
   kernels run in INTERPRET mode — correctness-comparable, not
   perf-comparable; the numbers become meaningful on silicon
   (`interpret` is recorded so readers can't misread CPU rows).
2. skewed partitioned join: hybrid (skew-aware dynamic build
   partitioning) vs the legacy grace path on a build whose single hot
   key previously forced the ENTIRE build through the partition loop —
   the acceptance scenario: hybrid spills zero partitions and must not
   lose to grace.
3. oversized cold partition: many sub-threshold keys crafted to hash
   into one partition whose build is 4x the batch budget. Legacy runs
   it as one oversized pass; recursive salted repartitioning
   (join_recursive_repartition, ISSUE 11) must bound every pass's build
   by the budget while returning identical rows.

Usage: python tools/join_bench.py [--rows N] [--build N] [--repeats N]
       [--no-detail]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))


def _best(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_probe_strategies(n_probe: int, n_build: int, repeats: int) -> dict:
    """Time the unique-join build+probe under each strategy through the
    REAL kernel entry points (ops/join.py), matches verified equal."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from starrocks_tpu.ops.join import hash_probe_rows
    from starrocks_tpu.ops.pallas_kernels import probe_searchsorted_pallas
    from starrocks_tpu.ops.segment import on_tpu

    rng = np.random.default_rng(7)
    bk = jnp.asarray(rng.permutation(n_build * 4)[:n_build].astype(np.int64))
    pk = jnp.asarray(rng.integers(0, n_build * 4, n_probe).astype(np.int64))
    interpret = not on_tpu()

    @jax.jit
    def sorted_path(bk, pk):
        order = jnp.argsort(bk, stable=True)
        bks = bk[order]
        pos = jnp.clip(jnp.searchsorted(bks, pk), 0, n_build - 1)
        match = bks[pos] == pk
        return match.sum(), order[pos]

    @jax.jit
    def ladder_path(bk, pk):
        order = jnp.argsort(bk, stable=True)
        bks = bk[order]
        pos = jnp.clip(probe_searchsorted_pallas(
            bks, pk, block=2048, interpret=interpret), 0, n_build - 1)
        match = bks[pos] == pk
        return match.sum(), order[pos]

    @jax.jit
    def hash_path(bk, pk):
        match, row = hash_probe_rows(
            bk, pk, n_build, jnp.ones(pk.shape, jnp.bool_))
        return match.sum(), row

    out = {"rows_probe": n_probe, "rows_build": n_build,
           "backend": jax.default_backend(), "interpret": interpret}
    counts = {}
    for name, fn in (("sorted", sorted_path), ("pallas_sorted", ladder_path),
                     ("pallas_hash", hash_path)):
        m, _ = fn(bk, pk)  # compile + correctness anchor
        counts[name] = int(m)
        best = _best(lambda: jax.block_until_ready(fn(bk, pk)), repeats)
        out[f"{name}_ms"] = round(best * 1000, 2)
        out[f"{name}_rows_per_sec"] = round(n_probe / best)
    assert len(set(counts.values())) == 1, f"strategy mismatch: {counts}"
    out["matches"] = counts["sorted"]
    return out


def bench_skewed_hybrid_vs_grace(n_probe: int, n_build: int, repeats: int,
                                 batch_rows: int) -> dict:
    """The acceptance A/B: one hot key owns half the build. Grace
    partitions + streams EVERYTHING; hybrid routes the hot key to the
    broadcast lane, keeps in-budget partitions resident, and spills only
    the overflow."""
    import numpy as np

    from starrocks_tpu.column import HostTable
    from starrocks_tpu.runtime.config import config
    from starrocks_tpu.runtime.session import Session
    from starrocks_tpu.storage.catalog import Catalog

    rng = np.random.default_rng(17)
    bk = rng.integers(0, n_build, n_build)
    bk[: n_build // 2] = 42  # the hot key owns half the build: under
    # grace ONE partition carries it, so every partition pass compiles
    # at (and argsorts) that inflated build capacity; the hybrid routes
    # it to the broadcast lane and sizes cold passes at the batch budget
    rng.shuffle(bk)
    cat = Catalog()
    cat.register("fact", HostTable.from_pydict({
        "k": list(rng.integers(0, int(n_build * 1.2), n_probe).astype(int)),
        "v": list(rng.integers(0, 100, n_probe).astype(int))}))
    cat.register("dim", HostTable.from_pydict({
        "k": list(bk.astype(int)),
        "w": list(rng.integers(0, 100, n_build).astype(int))}))
    s = Session(cat)
    q = "SELECT count(*) c, sum(v + w) sv FROM fact, dim WHERE fact.k = dim.k"
    old_t = config.get("batch_rows_threshold")
    old_b = config.get("spill_batch_rows")
    config.set("batch_rows_threshold", batch_rows)
    config.set("spill_batch_rows", batch_rows)
    out = {"rows_probe": n_probe, "rows_build": n_build,
           "batch_rows": batch_rows}
    try:
        results = {}
        for strat in ("auto", "grace"):
            config.set("join_hybrid_strategy", strat)
            results[strat] = s.sql(q).rows()  # compile + partition warmup
            best = _best(lambda: s.sql(q), repeats)
            key = "hybrid" if strat == "auto" else "grace"
            out[f"{key}_ms"] = round(best * 1000, 2)
            if strat == "auto":
                prof = s.last_profile
                ctr = {}

                def walk(p):
                    ctr.update(
                        {k: v for k, (v, _) in p.counters.items()})
                    for c in p.children:
                        walk(c)

                walk(prof)
                for k in ("join_skew_keys", "join_spilled_partitions",
                          "join_resident_partitions",
                          "join_skew_probe_rows"):
                    if k in ctr:
                        out[k] = int(ctr[k])
        assert results["auto"] == results["grace"], "hybrid != grace"
        out["hybrid_speedup"] = round(out["grace_ms"] / out["hybrid_ms"], 3)
    finally:
        config.set("batch_rows_threshold", old_t)
        config.set("spill_batch_rows", old_b)
        config.set("join_hybrid_strategy", "auto")
    return out


def bench_oversized_cold_recursion(repeats: int,
                                   batch_rows: int = 8192) -> dict:
    """A/B for recursive salted repartitioning (NEXT 11a): MANY distinct
    keys — every per-key count under the skew threshold, so nothing
    qualifies for the broadcast lane — crafted to hash into ONE cold
    partition. Legacy (`join_recursive_repartition=off`) must run that
    partition as a single pass whose build is 4x the batch budget; the
    recursion re-salts it into sub-passes, each within budget."""
    import numpy as np

    from starrocks_tpu.column import HostTable
    from starrocks_tpu.native import hash_partition_i64
    from starrocks_tpu.runtime.config import config
    from starrocks_tpu.runtime.session import Session
    from starrocks_tpu.storage.catalog import Catalog

    n_build = batch_rows * 4
    n_parts = 4  # == ceil(n_build / batch_rows) once every key is cold
    thresh = max(batch_rows // max(config.get("join_skew_factor"), 1), 1)
    per_key = max(thresh // 2, 1)
    need = -(-n_build // per_key)
    keys: list = []
    k = 0
    while len(keys) < need:  # mine keys that land in partition 0
        cand = np.arange(k, k + 100_000, dtype=np.int64)
        keys.extend(int(x) for x in cand[
            hash_partition_i64(cand, n_parts) == 0])
        k += 100_000
    keys = np.asarray(keys[:need], dtype=np.int64)
    rng = np.random.default_rng(23)
    bk = np.repeat(keys, per_key)[:n_build].copy()
    rng.shuffle(bk)
    pk = rng.choice(keys, n_build * 2)  # probe 2x build so dim stays the
    # build side under the DP join order

    cat = Catalog()
    cat.register("fact", HostTable.from_pydict({
        "k": list(int(x) for x in pk),
        "v": list(int(x) for x in rng.integers(0, 100, pk.size))}))
    cat.register("dim", HostTable.from_pydict({
        "k": list(int(x) for x in bk),
        "w": list(int(x) for x in rng.integers(0, 100, n_build))}))
    s = Session(cat)
    q = "SELECT count(*) c, sum(v + w) sv FROM fact, dim WHERE fact.k = dim.k"
    old_t = config.get("batch_rows_threshold")
    old_b = config.get("spill_batch_rows")
    config.set("batch_rows_threshold", batch_rows)
    config.set("spill_batch_rows", batch_rows)
    out = {"rows_probe": int(pk.size), "rows_build": n_build,
           "batch_rows": batch_rows, "distinct_keys": int(keys.size)}
    try:
        results = {}
        for mode in (True, False):
            config.set("join_recursive_repartition", mode)
            results[mode] = s.sql(q).rows()
            ctr = {}

            def walk(p):
                ctr.update({k: v for k, (v, _) in p.counters.items()})
                for c in p.children:
                    walk(c)

            walk(s.last_profile)
            key = "recursive" if mode else "legacy"
            for c in ("join_max_pass_build", "join_subpartitions",
                      "join_oversized_passes", "join_spilled_partitions"):
                if c in ctr:
                    out[f"{key}_{c[5:]}"] = int(ctr[c])
            best = _best(lambda: s.sql(q), repeats)
            out[f"{key}_ms"] = round(best * 1000, 2)
        assert results[True] == results[False], "recursive != legacy rows"
        assert out["legacy_max_pass_build"] > batch_rows, (
            "scenario failed to build an oversized cold partition")
        assert out["recursive_max_pass_build"] <= batch_rows, (
            "recursion left a pass above the batch budget")
        out["recursion_speedup"] = round(
            out["legacy_ms"] / out["recursive_ms"], 3)
    finally:
        config.set("batch_rows_threshold", old_t)
        config.set("spill_batch_rows", old_b)
        config.set("join_recursive_repartition", True)
    return out


def run_join_bench(rows: int = 1 << 20, build: int = 1 << 16,
                   repeats: int = 3, skew_batch: int = 65_536) -> dict:
    return {
        "probe_strategies": bench_probe_strategies(rows, build, repeats),
        "skewed_hybrid_vs_grace": bench_skewed_hybrid_vs_grace(
            rows, max(build * 2, 1 << 17), repeats, skew_batch),
        "oversized_cold_recursion": bench_oversized_cold_recursion(repeats),
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=1 << 20,
                    help="probe rows (default 1M)")
    ap.add_argument("--build", type=int, default=1 << 16,
                    help="build rows for the kernel A/B (default 64k)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--skew-batch", type=int, default=65_536,
                    help="spill batch rows for the hybrid/grace A/B")
    ap.add_argument("--no-detail", action="store_true",
                    help="do not merge into BENCH_DETAIL.json")
    args = ap.parse_args()

    res = run_join_bench(args.rows, args.build, args.repeats,
                         args.skew_batch)
    print(json.dumps(res, indent=1))
    if not args.no_detail:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "..", "BENCH_DETAIL.json")
        detail = {}
        if os.path.exists(path):
            try:
                with open(path) as f:
                    detail = json.load(f)
            except Exception as e:  # noqa: BLE001 — a corrupt detail file must not kill the bench
                print(f"# BENCH_DETAIL.json unreadable ({e}); rewriting",
                      file=sys.stderr)
        detail["join_bench"] = res
        with open(path, "w") as f:
            json.dump(detail, f, indent=1)
        print(f"# merged into {os.path.normpath(path)}", file=sys.stderr)


if __name__ == "__main__":
    main()
