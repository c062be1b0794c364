#!/usr/bin/env python
"""Dictionary-predicate probe: what does each way of turning a set of TRUE
codes of a dictionary column into a row mask cost?

    python tools/dict_predicate_probe.py       (one chip command; needs a TPU)
    JAX_PLATFORMS=cpu python tools/dict_predicate_probe.py --scale 0.001 --allow-cpu
    JAX_PLATFORMS=cpu python tools/dict_predicate_probe.py --compile-only
                    (compiles every candidate for a described v5e chip at the
                     real shapes: what XLA makes of each, no times)

`exprs/compile.dict_code_mask` gives the mask of a string IN list, LIKE or
any other boolean predicate over a dictionary column. This times, over
74,989,568 int32 codes (SSB's flat table, one chip's share) and 60,005,376
(TPC-H SF10's `lineitem`), 1% of the rows NULL with a code outside the
dictionary:

- `lut`: the formulation before PR 33, a boolean table over the dictionary
  gathered a row, `table[clip(code, 0, D - 1)]`, at dictionary lengths D of
  5, 25, 250, 1,000 and 40,000 (and at 64 and 65, either side of the length
  up to which XLA's TPU compiler expands a constant table into selects);
- `ranges/eq@R`: the OR over R runs of ONE code each of `code == lo`, R of 1,
  2, 4, ... 64 (and to 1,024 at D 40,000, to see where it stops winning);
- `ranges/span@R`: the same with runs of two codes or more,
  `(code >= lo) & (code <= hi)`;
- `engine`: `dict_code_mask` itself, as the tree has it, on the set of
  `ranges/eq@2` (SSB Q3.3's shape: two cities of 250);

each alone (`... /alone`: the mask is the output) and fused with two int32
range compares on a second column, the date filter beside it in Q3.3
(`.../dated`). `dates/only` is that date filter alone: the floor of a pass.

Each line: milliseconds (best of `--runs` after a warm-up call), the compile's
seconds, the compiled module's gather / fusion / select / compare counts, and
whether the mask equals numpy's on every row that is not NULL. Also written
to chiprun_out/dict_predicate_probe.json. No cell runs this.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SHAPES = (("ssb_flat", 74_989_568), ("tpch_lineitem", 60_005_376))
DICT_LENGTHS = (5, 25, 250, 1_000, 40_000)
EDGE_LENGTHS = (64, 65)  # `lut` only: the longest table XLA expands, and one more
RUNS = (1, 2, 4, 8, 16, 32, 64)
MORE_RUNS = (128, 256, 512, 1_024)  # at the longest dictionary only
DATE_LO, DATE_HI = 9_862, 10_226  # 1997-01-01 .. 1997-12-31, of 1992..1998


def _run_sets(D: int):
    """name -> sorted TRUE codes: R runs spread over a D-entry dictionary,
    the first touching code 0 and the last code D - 1."""
    import numpy as np

    sets = {}
    for R in RUNS + (MORE_RUNS if D == DICT_LENGTHS[-1] else ()):
        if 2 * R - 1 <= D:
            sets[f"eq@{R}"] = np.unique(
                np.linspace(0, D - 1, R).round().astype(np.int64)
                if R > 1 else np.array([D // 2]))
        if R in RUNS and D in (25, 250) and 3 * R <= D:
            lo = (np.linspace(0, D - 2, R).round().astype(np.int64)
                  if R > 1 else np.array([D // 2]))
            sets[f"span@{R}"] = np.unique(np.concatenate([lo, lo + 1]))
    return sets


def _runs_of(codes):
    """Sorted codes -> [(lo, hi)] of its maximal runs of consecutive codes."""
    import numpy as np

    cut = np.flatnonzero(np.diff(codes) != 1)
    los = np.concatenate([codes[:1], codes[cut + 1]])
    his = np.concatenate([codes[cut], codes[-1:]])
    return [(int(a), int(b)) for a, b in zip(los, his)]


def _candidates(D: int):
    """name -> (fn(codes) -> bool mask, sorted TRUE codes)."""
    import jax.numpy as jnp
    import numpy as np

    def lut_of(true):
        table = np.zeros((D,), np.bool_)
        table[true] = True
        return table

    def lut(true):
        table = lut_of(true)
        return lambda c: jnp.asarray(table)[jnp.clip(c, 0, D - 1)]

    def ranges(true):
        runs = _runs_of(true)

        def fn(c):
            m = None
            for lo, hi in runs:
                r = (c == lo) if lo == hi else ((c >= lo) & (c <= hi))
                m = r if m is None else (m | r)
            return m
        return fn

    def engine(true):
        from starrocks_tpu.exprs.compile import dict_code_mask

        table = lut_of(true)
        return lambda c: dict_code_mask(c, table)

    sets = _run_sets(D)
    out = {"lut": (lut(sets["eq@2"]), sets["eq@2"])}
    if D in EDGE_LENGTHS:
        return out
    for name, true in sets.items():
        out[f"ranges/{name}"] = (ranges(true), true)
    out["engine"] = (engine(sets["eq@2"]), sets["eq@2"])
    return out


def _counts(text: str) -> dict:
    """What the optimized module is made of: a table gathered a row is a
    `gather`; a table XLA expanded is a chain of `select`s in one fusion."""
    return {k: len(re.findall(rf" {k}\(", text))
            for k in ("gather", "fusion", "select", "compare")}


def _time(compiled, args, runs: int):
    import jax

    out = jax.block_until_ready(compiled(*args))  # warm-up
    best = None
    for _ in range(runs):
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return out, best


def probe(shape: str, n: int, D: int, runs: int, only=(), compile_only=False,
          dates=None):
    """One dictionary length at one row count. `dates` = (device or described
    array, host array or None) of the second column."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    print(f"shape {shape}: {n} rows, dictionary of {D}", flush=True)
    if compile_only:
        codes, host, valid = jax.ShapeDtypeStruct(
            (n,), jnp.int32, sharding=dates[0].sharding), None, None
    else:
        rng = np.random.default_rng(n % 9973 + D)
        host = rng.integers(0, D, size=n, dtype=np.int32)
        valid = rng.random(n) >= 0.01
        host[~valid] = np.where(rng.random(int((~valid).sum())) < 0.5, -1, D)
        codes = jnp.asarray(host)
    in_dates = None if dates[1] is None else (
        (dates[1] >= DATE_LO) & (dates[1] <= DATE_HI))

    def dated(fn):
        return lambda c, d: fn(c) & (d >= DATE_LO) & (d <= DATE_HI)

    jobs = []
    if D == DICT_LENGTHS[0]:
        jobs.append(("dates/only", lambda c, d: (d >= DATE_LO) & (d <= DATE_HI),
                     None, True))
    for cname, (fn, true) in _candidates(D).items():
        jobs.append((f"{cname}/alone", lambda c, d, fn=fn: fn(c), true, False))
        jobs.append((f"{cname}/dated", dated(fn), true, True))
    rows = []
    for cname, fn, true, with_dates in jobs:
        if only and not any(cname.startswith(o) for o in only):
            continue
        row = {"shape": shape, "rows": n, "dict": D, "candidate": cname,
               "true_codes": None if true is None else len(true),
               "runs": None if true is None else len(_runs_of(true))}
        try:
            t0 = time.perf_counter()
            compiled = jax.jit(fn).lower(codes, dates[0]).compile()
            row["compile_s"] = time.perf_counter() - t0
            row.update(_counts(compiled.as_text()))
            if not compile_only:
                out, dt = _time(compiled, (codes, dates[0]), runs)
                row["ms"] = dt * 1e3
                row["ns_per_row"] = dt * 1e9 / n
                want = (np.ones((n,), np.bool_) if true is None
                        else np.isin(host, true))
                if with_dates:
                    want &= in_dates
                keep = valid if true is not None else slice(None)
                row["equal"] = bool(
                    np.array_equal(np.asarray(out)[keep], want[keep]))
                del out, want
        except Exception as e:  # noqa: BLE001 — a candidate the compiler
            # refuses is a finding; the probe goes on
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(f"  {cname:24s} FAILED {row['error']}", flush=True)
            rows.append(row)
            continue
        rows.append(row)
        print(f"  {cname:24s} {row.get('ms', float('nan')):10.3f} ms  "
              f"{row.get('ns_per_row', float('nan')):7.4f} ns/row  "
              f"true {row['true_codes']} runs {row['runs']}  "
              f"gather {row['gather']} fusion {row['fusion']} "
              f"select {row['select']:3d} compare {row['compare']:4d}  "
              f"compile {row['compile_s']:5.1f} s  equal={row.get('equal')}",
              flush=True)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0,
                    help="cut the row counts (CPU rehearsal)")
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--only", default="",
                    help="comma-separated candidate prefixes, all if empty")
    ap.add_argument("--dicts", default=",".join(
        map(str, sorted(DICT_LENGTHS + EDGE_LENGTHS))))
    ap.add_argument("--shapes", default=",".join(s for s, _ in SHAPES))
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--compile-only", action="store_true",
                    help="compile for a described v5e chip; nothing runs")
    a = ap.parse_args()

    if a.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np

    import starrocks_tpu  # noqa: F401 — x64 on, as every engine program has it

    if a.compile_only:
        # a compile for a described chip cannot be read back from the cache
        jax.config.update("jax_enable_compilation_cache", False)
    dev = jax.devices()[0]
    print(f"backend {jax.default_backend()} device_kind {dev.device_kind}"
          f"{' (compile only: v5e described)' if a.compile_only else ''}",
          flush=True)
    if (jax.default_backend() != "tpu" and not a.allow_cpu
            and not a.compile_only):
        print("dict_predicate_probe: needs a TPU (--allow-cpu rehearses the "
              "script only; its times are not device times)")
        return 1
    rows = []
    for shape, n in SHAPES:
        if shape not in a.shapes.split(","):
            continue
        n = max(1024, int(n * a.scale) // 1024 * 1024)
        if a.compile_only:
            from jax.experimental import topologies
            from jax.sharding import SingleDeviceSharding

            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
            dates = (jax.ShapeDtypeStruct(
                (n,), jnp.int32,
                sharding=SingleDeviceSharding(topo.devices[0])), None)
        else:
            host_dates = np.random.default_rng(n % 9973).integers(
                8_035, 10_592, size=n, dtype=np.int32)
            dates = (jnp.asarray(host_dates), host_dates)
        for D in map(int, a.dicts.split(",")):
            rows += probe(shape, n, D, a.runs,
                          only=tuple(filter(None, a.only.split(","))),
                          compile_only=a.compile_only, dates=dates)
    table = {"backend": jax.default_backend(), "device_kind": dev.device_kind,
             "compile_only": a.compile_only, "scale": a.scale, "rows": rows}
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_name = ("dict_predicate_probe_compile.json" if a.compile_only
                else "dict_predicate_probe.json")
    with open(os.path.join(out_dir, out_name), "w") as f:
        json.dump(table, f, indent=1)
    bad = [r for r in rows if r.get("equal") is False or "error" in r]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
