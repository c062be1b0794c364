#!/usr/bin/env python
"""Compaction probe: what does each way of moving live rows to the front cost?

    python tools/compact_probe.py            (one chip command; needs a TPU)
    JAX_PLATFORMS=cpu python tools/compact_probe.py --scale 0.001 --allow-cpu

`ops/common.compact` shrinks a chunk to its live rows (stable order). This
times, at the two shapes of TPC-H SF10 Q3 (`shrink_0mwb`: 60,005,376 rows ->
36,584,448 slots, three int64 columns, 61% live; `shrink_0mw`: 36,584,448 ->
385,024, three int64 and three int32 columns, 0.8% live):

- `scatter/column`: the formulation before PR 25, one scatter a column;
- four ways to compute ONE int32 source-row index (slot j <- j-th live row):
  `sort` (single-operand lax.sort of `where(live, i, i | 1 << 31)`),
  `search` (searchsorted on the prefix sum), `scatter1` (arange scattered once,
  unique indices), `shift` (log2(cap) rounds of shift-by-2^b and select on the
  per-row displacement: elementwise, no gather, scatter or sort);
- the gathers of the columns through that index, plain and with the
  sorted/in-bounds promises;
- the columns carried through the sort or the shift rounds as payload, no
  gather at all;
- `engine`: `ops/common.compact` itself, as the tree has it.

Each line: milliseconds (best of `--runs` after a warm-up call), ns per input
row and per output row, the program's own bytes (XLA's memory analysis:
temporaries + outputs, arguments apart) and the process's
`peak_bytes_in_use` after it (a high-water mark: it only ever rises, so
candidates run cheapest first). Every candidate's result is compared with the
scatter's. Also written to chiprun_out/compact_probe.json. No cell runs this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SHAPES = (  # name, cap, out_cap, live share, int64 columns, int32 columns
    ("shrink_0mwb", 60_005_376, 36_584_448, 0.6085, 3, 0),
    ("shrink_0mw", 36_584_448, 385_024, 0.008, 3, 3),
)


def _candidates(cap: int, out_cap: int):
    """name -> (kind, fn). kind 'index': fn(live) -> src int32[out_cap];
    'gather': fn(src, cols) -> cols; 'whole': fn(live, cols) -> cols."""
    import jax.numpy as jnp
    from jax import lax

    slots = jnp.arange(out_cap, dtype=jnp.int32)

    def positions(live):
        pos = jnp.cumsum(jnp.asarray(live, jnp.int32)) - 1
        idx = jnp.where(live, pos, out_cap)
        return jnp.where(idx >= out_cap, out_cap, idx)

    def scatter_columns(live, cols):
        idx = positions(live)
        return tuple(jnp.zeros((out_cap,), a.dtype).at[idx].set(a, mode="drop")
                     for a in cols)

    def index_sort(live):
        i = jnp.arange(cap, dtype=jnp.uint32)
        key = jnp.where(live, i, i | jnp.uint32(1 << 31))
        return jnp.asarray(lax.sort(key, is_stable=False)[:out_cap]
                           & jnp.uint32((1 << 31) - 1), jnp.int32)

    def index_search(live):
        cs = jnp.cumsum(jnp.asarray(live, jnp.int32))
        return jnp.asarray(jnp.searchsorted(cs, slots + 1, side="left"),
                           jnp.int32)

    def index_scatter1(live):
        pos = jnp.cumsum(jnp.asarray(live, jnp.int32)) - 1
        i = jnp.arange(cap, dtype=jnp.int32)
        # dead and overflowing rows go to distinct slots past the end
        idx = jnp.where(live & (pos < out_cap), pos, out_cap + i)
        return jnp.zeros((out_cap,), jnp.int32).at[idx].set(
            i, mode="drop", unique_indices=True)

    def shift_rounds(d, payload=()):
        """d: rows to move left by (dead rows before this one), -1 = no row.
        Round b moves the rows whose bit b of d is set by 2^b; low bit first,
        two live rows never meet (their distance exceeds the difference of
        what they have still to move)."""
        for b in range(max(cap - 1, 1).bit_length()):
            s = 1 << b
            sh = jnp.concatenate([d[s:], jnp.full((s,), -1, d.dtype)])
            take = (sh >= 0) & (((sh >> b) & 1) == 1)
            stay = (d >= 0) & (((d >> b) & 1) == 0)
            payload = tuple(
                jnp.where(take, jnp.concatenate(
                    [a[s:], jnp.zeros((s,), a.dtype)]), a)
                for a in payload)
            d = jnp.where(take, sh, jnp.where(stay, d, -1))
        return d, payload

    def displacement(live):
        dead = jnp.cumsum(jnp.asarray(~live, jnp.int32))
        return jnp.where(live, dead, -1)

    def index_shift(live):
        d, _ = shift_rounds(displacement(live))
        d = d[:out_cap]
        return jnp.where(d >= 0, slots + d, cap - 1)

    def gather_plain(src, cols):
        return tuple(a[src] for a in cols)

    def gather_promised(src, cols):
        return tuple(a.at[src].get(mode="promise_in_bounds",
                                   indices_are_sorted=True) for a in cols)

    def mask(live, cols):
        keep = slots < jnp.sum(live)
        return tuple(jnp.where(keep, a, jnp.zeros((), a.dtype)) for a in cols)

    def whole(index_fn, gather_fn=gather_plain):
        def fn(live, cols):
            return mask(live, gather_fn(index_fn(live), cols))
        return fn

    def sort_payload(live, cols):
        i = jnp.arange(cap, dtype=jnp.uint32)
        key = jnp.where(live, i, i | jnp.uint32(1 << 31))
        out = lax.sort((key,) + tuple(cols), num_keys=1, is_stable=False)
        return mask(live, tuple(a[:out_cap] for a in out[1:]))

    def shift_payload(live, cols):
        _, out = shift_rounds(displacement(live), tuple(cols))
        return mask(live, tuple(a[:out_cap] for a in out))

    def engine(live, cols):
        from starrocks_tpu import types as T
        from starrocks_tpu.column.column import Chunk, Field, Schema
        from starrocks_tpu.ops.common import compact

        schema = Schema(tuple(
            Field(f"c{i}", T.BIGINT if a.dtype == jnp.int64 else T.INT, False)
            for i, a in enumerate(cols)))
        out, _ = compact(
            Chunk(schema, tuple(cols), (None,) * len(cols), live), out_cap)
        return tuple(out.data)

    return {
        "cumsum": ("index", lambda live: jnp.cumsum(
            jnp.asarray(live, jnp.int32))),
        "index/shift": ("index", index_shift),
        "index/sort": ("index", index_sort),
        "index/scatter1": ("index", index_scatter1),
        "index/search": ("index", index_search),
        "gather/plain": ("gather", gather_plain),
        "gather/promised": ("gather", gather_promised),
        "engine": ("whole", engine),
        "shift+gather": ("whole", whole(index_shift, gather_promised)),
        "sort+gather": ("whole", whole(index_sort, gather_promised)),
        "search+gather": ("whole", whole(index_search, gather_promised)),
        "scatter1+gather": ("whole", whole(index_scatter1, gather_promised)),
        "shift/payload": ("whole", shift_payload),
        "sort/payload": ("whole", sort_payload),
        "scatter/column": ("whole", scatter_columns),
    }


def _time(fn, args, runs: int):
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    prog = (int(mem.temp_size_in_bytes + mem.output_size_in_bytes)
            if mem is not None else None)
    out = jax.block_until_ready(compiled(*args))  # warm-up
    best = None
    for _ in range(runs):
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return out, best, prog


def probe_shape(name, cap, out_cap, share, n64, n32, runs, only=()):
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    ks = jax.random.split(jax.random.PRNGKey(cap % 9973), 1 + n64 + n32)
    live = jax.random.uniform(ks[0], (cap,)) < share
    cols = tuple(jax.random.randint(k, (cap,), -(1 << 62), 1 << 62, jnp.int64)
                 for k in ks[1:1 + n64])
    cols += tuple(jax.random.randint(k, (cap,), 0, 1 << 30, jnp.int32)
                  for k in ks[1 + n64:])
    n_live = int(jnp.sum(live))
    print(f"shape {name}: cap {cap} -> out_cap {out_cap}, live {n_live}, "
          f"{n64} int64 + {n32} int32 columns", flush=True)
    cands = _candidates(cap, out_cap)
    want, src, rows = None, None, []
    # the reference first (its result is what the others are held to), its
    # timing line printed in its place
    order = ["scatter/column"] + [k for k in cands if k != "scatter/column"]
    for cname in order:
        if only and cname not in only:
            continue
        kind, fn = cands[cname]
        args = {"index": (live,), "gather": (src, cols),
                "whole": (live, cols)}[kind]
        if kind == "gather" and src is None:
            continue
        try:
            out, dt, prog = _time(fn, args, runs)
        except Exception as e:  # noqa: BLE001 — a candidate the compiler or
            # the memory refuses is a finding; the probe goes on
            rows.append({"shape": name, "candidate": cname,
                         "error": f"{type(e).__name__}: {str(e)[:200]}"})
            print(f"  {cname:18s} FAILED {rows[-1]['error']}", flush=True)
            continue
        ok = None
        if cname == "scatter/column":
            want = out
        elif kind == "whole" and want is not None:
            ok = all(bool(jnp.array_equal(a, b)) for a, b in zip(out, want))
        elif kind == "index" and cname != "cumsum":
            # the first index is held to the scatter by its `+gather` line;
            # the others to the first, over the slots that hold a row
            k = min(n_live, out_cap)
            if src is None:
                src = out
            else:
                ok = bool(jnp.array_equal(out[:k], src[:k]))
        stats = dev.memory_stats() or {}
        rows.append({
            "shape": name, "candidate": cname, "ms": dt * 1e3,
            "ns_per_input_row": dt * 1e9 / cap,
            "ns_per_output_row": dt * 1e9 / out_cap,
            "program_bytes": prog,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "same_as_scatter": ok})
        r = rows[-1]
        print(f"  {cname:18s} {r['ms']:10.2f} ms  {r['ns_per_input_row']:8.3f}"
              f" ns/in  {r['ns_per_output_row']:9.3f} ns/out  program "
              f"{(prog or 0) / 1e6:8.1f} MB  peak "
              f"{(r['peak_bytes_in_use'] or 0) / 1e6:8.1f} MB  same={ok}",
              flush=True)
        del out
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0,
                    help="cut both shapes' row counts (CPU rehearsal)")
    ap.add_argument("--runs", type=int, default=2)
    ap.add_argument("--only", default="",
                    help="comma-separated candidates, all if empty (results "
                         "are compared only if `scatter/column` is among them)")
    ap.add_argument("--allow-cpu", action="store_true")
    a = ap.parse_args()

    import jax

    import starrocks_tpu  # noqa: F401 — x64 on, as every engine program has it

    dev = jax.devices()[0]
    print(f"backend {jax.default_backend()} device_kind {dev.device_kind}",
          flush=True)
    if jax.default_backend() != "tpu" and not a.allow_cpu:
        print("compact_probe: needs a TPU (--allow-cpu rehearses the script "
              "only; its times are not device times)")
        return 1
    rows = []
    for name, cap, out_cap, share, n64, n32 in SHAPES:
        cap = max(1024, int(cap * a.scale) // 1024 * 1024)
        out_cap = max(1024, int(out_cap * a.scale) // 1024 * 1024)
        rows += probe_shape(name, cap, out_cap, share, n64, n32, a.runs,
                            only=tuple(filter(None, a.only.split(","))))
    table = {"backend": jax.default_backend(), "device_kind": dev.device_kind,
             "scale": a.scale, "rows": rows}
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "compact_probe.json"), "w") as f:
        json.dump(table, f, indent=1)
    bad = [r for r in rows if r.get("same_as_scatter") is False]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
