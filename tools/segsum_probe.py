#!/usr/bin/env python
"""Segment-sum probe: what does each way of summing a GROUP BY's integer
columns per group cost?

    python tools/segsum_probe.py               (one chip command; needs a TPU)
    JAX_PLATFORMS=cpu python tools/segsum_probe.py --scale 0.001 --allow-cpu
    JAX_PLATFORMS=cpu python tools/segsum_probe.py --compile-only
                    (compiles every candidate for a described v5e chip at the
                     real shapes: program bytes and fusion counts, no times)

`ops/segment.seg_sums` gives every exact integer sum of one aggregate node.
This times, at TPC-H SF10 Q1's shape (60,005,376 rows; five int64 value
columns with Q1's bounds and one 0/1 count; 1.5% of the rows dead) and at a
quarter of the rows (one shard of four), for 6, 64 and 1,024 groups:

- `a/column-f32`: the formulation before PR 27, one call a column: 8-bit
  limbs stacked as f32, a one-hot of its own, `nbg,nbl->ngl` with the block
  the largest power of two dividing the row count;
- `b/contract@B`: ONE contraction over the 8-bit limbs of all columns side by
  side (41 for Q1), one one-hot, bf16 operands, f32 accumulation, row blocks
  of B whatever the row count (the tail padded with dead rows);
- `c16/masked@B`: no MXU, rows on the lane axis: per 16-bit limb a masked
  int32 reduction over blocks of B rows, widened to uint64 per block;
- `c64/masked`: the same with no limbs at all: `sum(where(gid == g, v, 0))` on
  the int64 column itself (XLA carries a 64-bit add as two u32 halves), one
  reduce a column; `c64/variadic`: all columns in one `lax.reduce`;
  `c64/rows-major`: the `[rows, G]` orientation `_seg_sum_float_bcast` has;
- `d/scan-b@B`, `d/scan-c64@B`: (b) and (c64) under a `fori_loop` over row
  blocks, so the temporaries are one block's whatever XLA fuses;
- `engine`: `ops/segment.seg_sums` itself, as the tree has it.
(PR 27 also timed `segment_sum_pallas` on one f32 column; the kernel went in
PR 28 and its readings stay in PERF.md section 6.)

Each line: milliseconds (best of `--runs` after a warm-up call), the
program's own bytes (XLA's memory analysis: temporaries + outputs, arguments
apart), the process's `peak_bytes_in_use` after it (a high-water mark), the
compiled module's fusion / convolution / reduce counts, and whether the sums
equal a host reference (numpy `bincount` over 16-bit limbs, recombined mod
2^64). Also written to chiprun_out/segsum_probe.json. No cell runs this.
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

Q1_ROWS = 60_005_376
SHAPES = (("q1", Q1_ROWS), ("q1_shard", 15_001_600))
GROUPS = (6, 64, 1024)
# (name, exclusive upper bound of the values, nbits the engine's caller passes)
COLUMNS = (
    ("l_quantity", 5_001, 64),
    ("l_extendedprice", 10_495_001, 64),
    ("disc_price", 1_049_500_001, 64),
    ("charge", 113_346_000_001, 64),
    ("l_discount", 11, 64),
    ("count", 2, 1),
)


def _nlimbs(nbits: int, bits: int) -> int:
    return max(1, -(-nbits // bits))


def _limbs(v, nbits: int, bits: int, dtype):
    import jax.numpy as jnp

    u = jnp.asarray(v, jnp.uint64)
    mask = (1 << bits) - 1
    return [((u >> (bits * j)) & mask).astype(dtype)
            for j in range(_nlimbs(nbits, bits))]


def _recombine(tot, nbits_of, bits: int):
    """tot: [G, L] uint64 limb totals, columns side by side -> one int64[G]
    a column."""
    import jax.numpy as jnp

    out, at = [], 0
    for nbits in nbits_of:
        acc = jnp.zeros(tot.shape[:1], jnp.uint64)
        for j in range(_nlimbs(nbits, bits)):
            acc = acc + (tot[:, at + j] << (bits * j))
        at += _nlimbs(nbits, bits)
        out.append(jnp.asarray(acc, jnp.int64))
    return tuple(out)


def _candidates(n: int, G: int, nbits_of):
    """name -> fn(cols, gid) -> tuple of int64[G]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    slots = jnp.arange(G, dtype=jnp.int32)

    def column_f32(cols, gid):
        block = min(n & -n, 32768)
        g = jnp.clip(gid, 0, G).reshape(-1, block)
        out = []
        for v, nbits in zip(cols, nbits_of):
            limbs = jnp.stack(_limbs(v, nbits, 8, jnp.float32), axis=-1)
            oh = (g[:, :, None] == jnp.arange(G + 1, dtype=jnp.int32)
                  ).astype(jnp.float32)
            part = jnp.einsum("nbg,nbl->ngl", oh,
                              limbs.reshape(-1, block, limbs.shape[-1]))
            tot = jnp.sum(part.astype(jnp.uint64), axis=0)[:G]
            out += _recombine(tot, (nbits,), 8)
        return tuple(out)

    def padded(x, m: int, fill):
        return x if m == n else jnp.pad(x, (0, m - n), constant_values=fill)

    def contract_block(limb_cols, g):
        """[nb, B] rows -> [G, L] uint64; B * 255 < 2^24 keeps f32 exact."""
        oh = (g[:, :, None] == slots).astype(jnp.bfloat16)
        limbs = jnp.stack(limb_cols, axis=-1)
        part = jnp.einsum("nbg,nbl->ngl", oh, limbs,
                          preferred_element_type=jnp.float32)
        return jnp.sum(part.astype(jnp.uint64), axis=0)

    def contract(block: int):
        def fn(cols, gid):
            m = -(-n // block) * block
            g = padded(gid, m, G).reshape(-1, block)
            limb_cols = [x.reshape(-1, block)
                         for v, nbits in zip(cols, nbits_of)
                         for x in _limbs(padded(v, m, 0), nbits, 8,
                                         jnp.bfloat16)]
            return _recombine(contract_block(limb_cols, g), nbits_of, 8)
        return fn

    def masked16(block: int):
        def fn(cols, gid):
            m = -(-n // block) * block
            eq = (padded(gid, m, G).reshape(-1, block)[:, None, :]
                  == slots[None, :, None])  # [nb, G, B]
            tots = []
            for v, nbits in zip(cols, nbits_of):
                for x in _limbs(padded(v, m, 0), nbits, 16, jnp.int32):
                    part = jnp.sum(
                        jnp.where(eq, x.reshape(-1, 1, block), 0), axis=2)
                    tots.append(jnp.sum(part.astype(jnp.uint64), axis=0))
            return _recombine(jnp.stack(tots, axis=-1), nbits_of, 16)
        return fn

    def as_i64(v):
        return jnp.asarray(v, jnp.int64)

    def masked64(cols, gid):
        eq = slots[:, None] == gid[None, :]
        return tuple(jnp.sum(jnp.where(eq, as_i64(v)[None, :], 0), axis=1)
                     for v in cols)

    def masked64_rows_major(cols, gid):
        eq = gid[:, None] == slots[None, :]
        return tuple(jnp.sum(jnp.where(eq, as_i64(v)[:, None], 0), axis=0)
                     for v in cols)

    def masked64_variadic(cols, gid):
        eq = slots[:, None] == gid[None, :]
        ops = tuple(jnp.where(eq, as_i64(v)[None, :], 0) for v in cols)
        zero = tuple(jnp.zeros((), jnp.int64) for _ in cols)
        return tuple(lax.reduce(
            ops, zero, lambda a, b: tuple(x + y for x, y in zip(a, b)), (1,)))

    def scanned(block: int, step):
        """acc += step(block of the columns, block of gid) over whole
        blocks in a fori_loop, then once over the (static) tail."""
        def fn(cols, gid):
            nb = n // block
            if nb == 0:
                return step(cols, gid)

            def body(i, acc):
                at = i * block
                part = step(
                    tuple(lax.dynamic_slice(v, (at,), (block,)) for v in cols),
                    lax.dynamic_slice(gid, (at,), (block,)))
                return tuple(a + p for a, p in zip(acc, part))

            acc = tuple(jnp.zeros((G,), jnp.int64) for _ in cols)
            acc = lax.fori_loop(0, nb, body, acc)
            if nb * block < n:
                part = step(tuple(v[nb * block:] for v in cols),
                            gid[nb * block:])
                acc = tuple(a + p for a, p in zip(acc, part))
            return acc
        return fn

    def contract_step(cols, gid):
        b = gid.shape[0]
        inner = min(b & -b, 32768)
        limb_cols = [x.reshape(-1, inner) for v, nbits in zip(cols, nbits_of)
                     for x in _limbs(v, nbits, 8, jnp.bfloat16)]
        return _recombine(contract_block(limb_cols, gid.reshape(-1, inner)),
                          nbits_of, 8)

    def engine(cols, gid):
        from starrocks_tpu.ops import segment

        return tuple(segment.seg_sums(list(zip(cols, nbits_of)), gid, G))

    return {
        "c64/masked": masked64,
        "c64/variadic": masked64_variadic,
        "c64/rows-major": masked64_rows_major,
        "d/scan-c64@1M": scanned(1 << 20, masked64),
        "engine": engine,
        "c16/masked@8192": masked16(8192),
        "c16/masked@32768": masked16(32768),
        "d/scan-b@1M": scanned(1 << 20, contract_step),
        "b/contract@1024": contract(1024),
        "b/contract@32768": contract(32768),
        "a/column-f32": column_f32,
    }


def _reference(cols, gid, G: int):
    """Per-group sums mod 2^64 on the host: bincount of 16-bit limbs in
    float64 (65,535 x 60M rows stays under 2^53), recombined in Python ints."""
    import numpy as np

    g = np.where((gid >= 0) & (gid < G), gid, G)
    out = []
    for v in cols:
        u = v.astype(np.int64).view(np.uint64)
        acc = [0] * G
        for j in range(4):
            limb = ((u >> np.uint64(16 * j)) & np.uint64(0xFFFF)).astype(
                np.float64)
            tot = np.bincount(g, weights=limb, minlength=G + 1)[:G]
            acc = [(a + (int(t) << (16 * j))) % (1 << 64)
                   for a, t in zip(acc, tot)]
        out.append(np.array(acc, np.uint64).view(np.int64))
    return out


def _counts(text: str) -> dict:
    """How many fusions, convolutions (a dot lowers to one) and reduces the
    optimized module's entry computation and loop bodies call."""
    return {"fusions": len(re.findall(r" fusion\(", text)),
            "convolutions": len(re.findall(r" convolution\(", text)),
            "reduces": len(re.findall(r" reduce\(", text)),
            "whiles": len(re.findall(r" while\(", text))}


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


def _host_data(n: int, G: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    cols = tuple(rng.integers(0, hi, size=n, dtype=np.int64)
                 for _, hi, _ in COLUMNS)
    gid = rng.integers(0, G, size=n, dtype=np.int32)
    gid[rng.random(n) < 0.015] = G  # the rows Q1's date filter drops
    gid[:2] = (G + 7, 2**31 - 1)  # past the spill slot too
    return cols, gid


def probe(name: str, n: int, G: int, runs: int, only=(), compile_only=False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    nbits_of = tuple(b for _, _, b in COLUMNS)
    cands = _candidates(n, G, nbits_of)
    print(f"shape {name}: {n} rows, {G} groups, {len(COLUMNS)} columns",
          flush=True)
    if compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])
        args = (tuple(jax.ShapeDtypeStruct(
            (n,), jnp.bool_ if b == 1 else jnp.int64, sharding=one)
            for b in nbits_of),
            jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one))
        dev, want = None, None
    else:
        dev = jax.devices()[0]
        host_cols, host_gid = _host_data(n, G, seed=n % 9973 + G)
        want = _reference(host_cols, host_gid, G)
        args = (tuple(jnp.asarray(v.astype(np.bool_) if b == 1 else v)
                      for v, b in zip(host_cols, nbits_of)),
                jnp.asarray(host_gid))
        del host_cols, host_gid
    rows = []
    for cname, fn in cands.items():
        if only and cname not in only:
            continue
        row = {"shape": name, "rows": n, "groups": G, "candidate": cname}
        try:
            t0 = time.perf_counter()
            compiled = jax.jit(fn).lower(*args).compile()
            row["compile_s"] = time.perf_counter() - t0
            mem = compiled.memory_analysis()
            row["program_bytes"] = (
                int(mem.temp_size_in_bytes + mem.output_size_in_bytes)
                if mem is not None else None)
            row.update(_counts(compiled.as_text()))
            if not compile_only:
                out, dt = _time(compiled, args, runs)
                row["ms"] = dt * 1e3
                row["ns_per_row"] = dt * 1e9 / n
                stats = dev.memory_stats() or {}
                row["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
                row["exact"] = all(
                    np.array_equal(np.asarray(a), b)
                    for a, b in zip(out, want))
                del out
        except Exception as e:  # noqa: BLE001 — a candidate the compiler or
            # the memory refuses is a finding; the probe goes on
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(f"  {cname:18s} FAILED {row['error']}", flush=True)
            rows.append(row)
            continue
        rows.append(row)
        print(f"  {cname:18s} {row.get('ms', float('nan')):10.2f} ms  "
              f"{row.get('ns_per_row', float('nan')):7.3f} ns/row  program "
              f"{(row['program_bytes'] or 0) / 1e6:9.1f} MB  peak "
              f"{(row.get('peak_bytes_in_use') or 0) / 1e6:9.1f} MB  "
              f"fusions {row['fusions']:3d} conv {row['convolutions']:2d} "
              f"reduce {row['reduces']:3d} while {row['whiles']}  compile "
              f"{row['compile_s']:5.1f} s  exact={row.get('exact')}",
              flush=True)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0,
                    help="cut the row counts (CPU rehearsal)")
    ap.add_argument("--runs", type=int, default=2)
    ap.add_argument("--only", default="",
                    help="comma-separated candidates, all if empty")
    ap.add_argument("--groups", default=",".join(map(str, GROUPS)))
    ap.add_argument("--shapes", default=",".join(s for s, _ in SHAPES))
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--compile-only", action="store_true",
                    help="compile for a described v5e chip; nothing runs")
    a = ap.parse_args()

    if a.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

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
        print("segsum_probe: needs a TPU (--allow-cpu rehearses the script "
              "only; its times are not device times)")
        return 1
    rows = []
    for name, n in SHAPES:
        if name not in a.shapes.split(","):
            continue
        n = max(1024, int(n * a.scale) // 1024 * 1024)
        for G in map(int, a.groups.split(",")):
            rows += probe(name, n, G, a.runs,
                          only=tuple(filter(None, a.only.split(","))),
                          compile_only=a.compile_only)
    table = {"backend": jax.default_backend(), "device_kind": dev.device_kind,
             "compile_only": a.compile_only, "scale": a.scale, "rows": rows}
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_name = ("segsum_probe_compile.json" if a.compile_only
                else "segsum_probe.json")
    with open(os.path.join(out_dir, out_name), "w") as f:
        json.dump(table, f, indent=1)
    bad = [r for r in rows if r.get("exact") is False]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
