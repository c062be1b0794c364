"""Sort-based hash join.

Reference behavior: be/src/exec/hash_joiner.h:192 + join_hash_map.h —
build/probe hash join with INNER/LEFT OUTER/RIGHT variants, SEMI/ANTI, and
build-side runtime filters. The TPU re-design replaces the pointer-chasing
hash table with: sort the (compacted) build side by key, binary-search probes
into it (jnp.searchsorted compiles to an XLA while-free ladder), and gather
payloads. Multi-column keys are packed into one int64 by the planner
(pack_keys) using key-range stats; that keeps probe a single vector compare.

Two shapes:
- unique build keys (PK-FK joins — the common TPC-H/SSB case): output rows
  = probe rows, pure gather, no expansion.
- duplicate build keys: run-length expansion via jnp.repeat with a static
  output capacity + true-size return for host-side overflow recompile.

NULL join keys never match (SQL equality semantics).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .. import types as T
from ..column.column import Chunk, Field, Schema
from ..exprs.compile import ExprCompiler
from ..exprs.ir import Col
from .common import eval_keys, mix64, phase

INNER = "inner"
LEFT_OUTER = "left_outer"
LEFT_SEMI = "left_semi"
LEFT_ANTI = "left_anti"

_I64MAX = jnp.iinfo(jnp.int64).max


def _pack_evals(keys, live, capacity: int, bit_widths):
    """Pack evaluated key EVals into one int64 per row (see pack_keys)."""
    ok = live
    for k in keys:
        if k.valid is not None:
            ok = ok & k.valid
    if len(keys) == 1 and bit_widths is None:
        packed = jnp.asarray(keys[0].data, jnp.int64)
    elif bit_widths == "hash":
        h = jnp.zeros((capacity,), jnp.uint64)
        cols = []
        for k in keys:
            kd = jnp.asarray(k.data)
            if kd.ndim == 2:  # rank-2 (DECIMAL128 limbs): hash each limb
                cols.extend(kd[:, j] for j in range(kd.shape[1]))
                continue
            if not jnp.issubdtype(kd.dtype, jnp.integer):
                kd = jnp.asarray(kd, jnp.float64)
                kd = jnp.where(kd == 0, 0.0, kd)  # -0.0 == +0.0 in SQL
                kd = kd.view(jnp.int64)
            cols.append(kd)
        for kd in cols:
            kh = mix64(jnp.asarray(kd, jnp.int64).view(jnp.uint64))
            # boost hash_combine: order-sensitive, avalanched
            h = mix64(h ^ (kh + jnp.uint64(0x9E3779B97F4A7C15)
                            + (h << 6) + (h >> 2)))
        packed = h.view(jnp.int64)
        # keep the NULL/dead sentinel unambiguous
        packed = jnp.where(packed == _I64MAX, _I64MAX - 1, packed)
    else:
        assert bit_widths is not None and len(bit_widths) == len(keys), (
            "multi-key join requires planner-provided bit widths"
        )
        packed = jnp.zeros((capacity,), jnp.int64)
        for k, w in zip(keys, bit_widths):
            kd = jnp.asarray(k.data, jnp.int64)
            packed = (packed << w) | (kd & ((1 << w) - 1))
    return jnp.where(ok, packed, _I64MAX), ok


def pack_keys(chunk: Chunk, key_exprs, bit_widths=None):
    """Evaluate key exprs and pack them into one int64 per row.

    bit_widths[i] = bits reserved for key i (from planner stats); when None a
    single key is used as-is. bit_widths="hash": combined keys don't fit 63
    bits — mix each key through splitmix64 into one 64-bit fingerprint
    (collisions possible: the PLANNER must re-verify equality with residual
    predicates; it forces the expansion join + eq residuals in that mode).
    NULL any-key or dead row -> sentinel INT64 MAX (sorts last, never
    matches a probe because probe NULLs are also masked).
    Returns (packed[cap] int64, ok[cap] bool) where ok = live & all keys valid.

    SINGLE-side callers only (exchange routing): dict-encoded string keys
    pack RAW codes. Anything comparing two chunks' keys must go through
    pack_key_pair, which aligns dictionaries first.
    """
    keys = eval_keys(chunk, key_exprs)
    return _pack_evals(keys, chunk.sel_mask(), chunk.capacity, bit_widths)


def _align_dict_keys(pks, bks):
    """Remap dict-encoded key pairs onto a shared merged dictionary.

    Per-column StringDicts assign codes independently, so raw-code equality
    across two tables is meaningless (t1.'b'==code 1 vs t2.'b'==code 0).
    Dictionaries are trace-time constants: merge once per key pair, remap
    both sides' codes through constant LUTs (reference analog: the global
    dict normalization in be/src/compute_env/global_dict/)."""
    out_p, out_b = [], []
    for p, b in zip(pks, bks):
        if p.dict is not None and b.dict is not None and p.dict is not b.dict:
            m, rp, rb = p.dict.merge(b.dict)
            lp = jnp.asarray(rp, jnp.int64)
            lb = jnp.asarray(rb, jnp.int64)
            pd = lp[jnp.clip(p.data, 0, max(len(p.dict) - 1, 0))] if len(
                p.dict) else jnp.asarray(p.data, jnp.int64)
            bd = lb[jnp.clip(b.data, 0, max(len(b.dict) - 1, 0))] if len(
                b.dict) else jnp.asarray(b.data, jnp.int64)
            p = dataclasses.replace(p, data=pd, dict=m)
            b = dataclasses.replace(b, data=bd, dict=m)
        out_p.append(p)
        out_b.append(b)
    return out_p, out_b


def align_chunk_dicts(lc: Chunk, rc: Chunk, probe_keys, build_keys):
    """Rewrite dict-encoded join-key COLUMNS of both chunks onto merged
    dictionaries (Col keys only). Needed when the two sides are routed
    independently — e.g. the distributed hash shuffle packs each side's
    codes separately, so equal strings must carry equal codes BEFORE the
    exchange, not just inside the join kernel."""
    for pk, bk in zip(probe_keys, build_keys):
        if not (isinstance(pk, Col) and isinstance(bk, Col)):
            continue
        fi = lc.schema.index(pk.name)
        gi = rc.schema.index(bk.name)
        fp, fb = lc.schema.fields[fi], rc.schema.fields[gi]
        if fp.dict is None or fb.dict is None or fp.dict is fb.dict:
            continue
        m, rp, rb = fp.dict.merge(fb.dict)

        def remap(chunk, i, f, lut, old_len, merged):
            codes = chunk.data[i]
            if old_len:
                codes = jnp.asarray(lut, jnp.int64)[
                    jnp.clip(codes, 0, old_len - 1)]
            data = chunk.data[:i] + (codes,) + chunk.data[i + 1:]
            fields = list(chunk.schema.fields)
            fields[i] = dataclasses.replace(f, dict=merged)
            return Chunk(Schema(tuple(fields)), data, chunk.valid, chunk.sel)

        lc = remap(lc, fi, fp, rp, len(fp.dict), m)
        rc = remap(rc, gi, fb, rb, len(fb.dict), m)
    return lc, rc


def pack_key_pair(probe: Chunk, build: Chunk, probe_keys, build_keys,
                  bit_widths=None):
    """pack_keys for a probe/build pair: aligns string dictionaries between
    the sides before packing so code equality means string equality.
    Returns (pk, p_ok, bk, b_ok)."""
    pks = eval_keys(probe, probe_keys)
    bks = eval_keys(build, build_keys)
    pks, bks = _align_dict_keys(pks, bks)
    pk, p_ok = _pack_evals(pks, probe.sel_mask(), probe.capacity, bit_widths)
    bk, b_ok = _pack_evals(bks, build.sel_mask(), build.capacity, bit_widths)
    return pk, p_ok, bk, b_ok


def _or_across_shards(lanes, axis: str):
    """Bitwise OR of per-shard uint8 0/1 lanes (the global-RF collective of
    the dense bitmap and the bloom bitset), as a 32-bit sum. NOT
    `lax.pmax` on uint8: on a four-chip v5e an 8-bit max all-reduce returned
    wrong lanes (57,956 of 65,536 differed from numpy) and TPC-H Q3's
    runtime filter dropped matching rows; 32-bit all-reduces were exact."""
    return jnp.asarray(
        jax.lax.psum(jnp.asarray(lanes, jnp.int32), axis) > 0, jnp.uint8)


def _min_max_across_shards(lo, hi, axis: str):
    """(min of `lo`, max of `hi`) over the shards, for int64 scalars: the n
    per-shard values gathered and reduced locally. NOT `lax.pmin`/`pmax`:
    the TPU compiler lowers a 64-bit all-reduce for sums only (int64
    pmin/pmax raise "Supported lowering only of Sum all reduce" on a v5e)."""
    return (jnp.min(jax.lax.all_gather(lo, axis)),
            jnp.max(jax.lax.all_gather(hi, axis)))


@phase("rf")
def runtime_filter_mask(
    probe: Chunk, build: Chunk, probe_keys, build_keys, bit_widths=None,
    axis: str | None = None, dense_range: tuple | None = None,
):
    """Build-side runtime filter applied to the probe (reference:
    be/src/exec_primitive/runtime_filter/ + global merge via
    orchestration/runtime_filter_worker.h:41). In the compiled world the
    "delivery" is dataflow: build-side summaries feed a probe mask inside
    the same program. Two strengths:

    - min/max range filter (always available); with `axis` the local bounds
      merge across shards (all_gather + local min/max) — the global-RF
      collective.
    - EXACT membership (IN-set) filter when the planner bounds the key range
      via catalog stats (`dense_range=(lo, hi)`): build keys scatter into a
      dense presence bitmap the probe gathers; with `axis` the bitmaps
      OR-merge across shards (`_or_across_shards`). Subsumes min/max —
      e.g. a filtered dimension build passes only its surviving keys.

    Only valid for INNER/LEFT SEMI joins (probe rows may be dropped)."""
    pk, p_ok, bk, b_ok = pack_key_pair(
        probe, build, probe_keys, build_keys, bit_widths)
    if dense_range is not None:
        lo, hi = dense_range
        size = int(hi - lo + 1)
        present = jnp.zeros((size,), jnp.uint8).at[
            jnp.where(b_ok, bk - lo, size)
        ].set(1, mode="drop")
        if axis is not None:
            present = _or_across_shards(present, axis)
        idx = pk - lo
        in_range = (idx >= 0) & (idx < size)
        hit = present[jnp.clip(idx, 0, size - 1)] == 1
        return in_range & hit
    bmin = jnp.min(jnp.where(b_ok, bk, _I64MAX))
    bmax = jnp.max(jnp.where(b_ok, bk, jnp.iinfo(jnp.int64).min))
    if axis is not None:
        bmin, bmax = _min_max_across_shards(bmin, bmax, axis)
    # All-NULL (or empty) build side: bmin stays I64MAX and bmax stays
    # I64MIN, so bmin > bmax and the conjunction below is ALL-FALSE. That is
    # the intended INNER/LEFT-SEMI semantics — an empty build key set
    # matches nothing, so every probe row may be dropped. A refactor that
    # "fixes" the inverted range into an all-true mask would silently keep
    # the whole probe (wrong only in performance for the filter itself, but
    # callers compact to the join estimate trusting the mask is a SUBSET of
    # matches). Regression-pinned by test_runtime_filters.py.
    return (pk >= bmin) & (pk <= bmax)


_BLOOM_SALT = 0x9E3779B97F4A7C15  # golden-ratio odd constant (2nd probe)


def bloom_build_bitset(bk, b_ok, bits: int, axis: str | None = None):
    """Build-side half of the bloom runtime filter: hash packed keys into a
    power-of-2 bit array (one uint8 lane per bit — the gather-friendly
    layout the dense bitmap already uses) via TWO independent splitmix64
    probes. With `axis` the bitsets OR-merge across shards
    (`_or_across_shards`), exactly like the dense presence bitmap — the
    global-RF collective."""
    assert bits & (bits - 1) == 0, "bloom bit count must be a power of 2"
    mask = jnp.uint64(bits - 1)
    h1 = mix64(jnp.asarray(bk, jnp.int64).view(jnp.uint64))
    h2 = mix64(h1 ^ jnp.uint64(_BLOOM_SALT))
    i1 = jnp.where(b_ok, jnp.asarray(h1 & mask, jnp.int64), bits)
    i2 = jnp.where(b_ok, jnp.asarray(h2 & mask, jnp.int64), bits)
    bitset = (
        jnp.zeros((bits,), jnp.uint8)
        .at[i1].set(1, mode="drop")
        .at[i2].set(1, mode="drop")
    )
    if axis is not None:
        bitset = _or_across_shards(bitset, axis)
    return bitset


def bloom_probe_bitset(bitset, pk, p_ok):
    """Probe-side half: a row survives iff BOTH of its key's bloom probes
    are set. Same hash chain as the build side, so a probe key equal to any
    build key ALWAYS hits both its bits — the filter can never false-
    negative (drop a matching row); collisions only keep extra rows, which
    the join itself re-verifies."""
    bits = bitset.shape[0]
    mask = jnp.uint64(bits - 1)
    h1 = mix64(jnp.asarray(pk, jnp.int64).view(jnp.uint64))
    h2 = mix64(h1 ^ jnp.uint64(_BLOOM_SALT))
    g1 = bitset[jnp.asarray(h1 & mask, jnp.int64)]
    g2 = bitset[jnp.asarray(h2 & mask, jnp.int64)]
    return p_ok & (pk != _I64MAX) & (g1 == 1) & (g2 == 1)


@phase("rf")
def bloom_filter_mask(
    probe: Chunk, build: Chunk, probe_keys, build_keys, bit_widths=None,
    axis: str | None = None, bits: int = 1 << 20,
):
    """Bloom-bitset runtime filter: near-exact membership for ANY key range
    — the strengths the dense bitmap can't reach (wide/sparse keys, hash-
    packed multi-key tuples, missing stats). Works on the SAME packed keys
    the join compares (dictionaries aligned by pack_key_pair), so equal
    keys hash equal on both sides and matching probe rows always survive.

    Only valid for INNER/LEFT SEMI joins (probe rows may be dropped); NULL
    probe keys never match and are dropped, per SQL equality semantics."""
    pk, p_ok, bk, b_ok = pack_key_pair(
        probe, build, probe_keys, build_keys, bit_widths)
    bitset = bloom_build_bitset(bk, b_ok, bits, axis)
    return bloom_probe_bitset(bitset, pk, p_ok)


def dense_semi_anti_mask(probe: Chunk, build: Chunk, probe_keys, build_keys,
                         dense_range, anti: bool):
    """EXACT SEMI/ANTI join as one presence-bitmap test: for a
    stats-bounded single key, membership in the build's key set IS the
    whole join — no build sort, no probe search (the dominant cost of
    EXISTS/IN against big builds, e.g. TPC-H Q4's filtered-lineitem
    probe). NULL probe keys never match (kept by ANTI, dropped by SEMI),
    per SQL semantics."""
    pk, p_ok, bk, b_ok = pack_key_pair(probe, build, probe_keys, build_keys)
    lo, hi = dense_range
    size = int(hi - lo + 1)
    with phase("build"):
        present = jnp.zeros((size,), jnp.uint8).at[
            jnp.where(b_ok, bk - lo, size)
        ].set(1, mode="drop")
    with phase("probe"):
        idx = pk - lo
        in_range = (idx >= 0) & (idx < size)
        member = p_ok & in_range & (present[jnp.clip(idx, 0, size - 1)] == 1)
    return ~member if anti else member


def _merge_schemas(left: Chunk, right: Chunk, right_names) -> tuple:
    lnames = set(left.schema.names)
    out_fields = list(left.schema.fields)
    for n in right_names:
        f = right.schema.field(n)
        if n in lnames:
            raise ValueError(f"duplicate output column {n!r} in join")
        out_fields.append(f)
    return tuple(out_fields)


def hash_join_unique(
    probe: Chunk,
    build: Chunk,
    probe_keys,
    build_keys,
    join_type: str = INNER,
    payload=None,  # build column names to attach; default all
    bit_widths=None,
    build_order=None,  # precomputed argsort of the packed build keys
):
    """Join where build keys are unique (validated by planner/caller).

    Output chunk has probe's capacity: probe columns + gathered build payload.
    """
    payload = list(payload if payload is not None else build.schema.names)
    pk, p_ok, bk, _b_ok = pack_key_pair(
        probe, build, probe_keys, build_keys, bit_widths
    )  # build NULL/dead rows pack to the sentinel
    bcap = build.capacity

    with phase("build"):
        order = (build_order if build_order is not None
                 else jnp.argsort(bk, stable=True))  # sentinels go last
        bk_sorted = bk[order]

    with phase("probe"):
        pos = jnp.searchsorted(bk_sorted, pk)
        pos_c = jnp.clip(pos, 0, bcap - 1)
        match = (bk_sorted[pos_c] == pk) & p_ok & (pk != _I64MAX)
        build_row = order[pos_c]
    return _unique_join_epilogue(
        probe, build, payload, match, build_row, join_type)


@phase("payload")
def _unique_join_epilogue(probe, build, payload, match, build_row, join_type):
    """Shared tail of the 1:N join kernels (sorted + LUT): gather the build
    payload by matched row, NULL-mask non-matches for LEFT OUTER, and apply
    the join-type selection semantics at probe capacity."""
    data = list(probe.data)
    valid = list(probe.valid)
    for n in payload:
        i = build.schema.index(n)
        d = build.data[i][build_row]
        v = build.valid[i]
        v = None if v is None else v[build_row]
        if join_type == LEFT_OUTER:
            # non-matching rows carry NULL build columns
            mv = match if v is None else (v & match)
            v = mv
        data.append(d)
        valid.append(v)

    sel = probe.sel_mask()
    if join_type == INNER:
        sel = sel & match
    elif join_type == LEFT_SEMI:
        return probe.and_sel(match)
    elif join_type == LEFT_ANTI:
        return probe.and_sel(~match)
    elif join_type != LEFT_OUTER:
        raise NotImplementedError(join_type)
    out_fields = _merge_schemas(probe, build, payload)
    return Chunk(Schema(out_fields), tuple(data), tuple(valid), sel)


def hash_join_lut(
    probe: Chunk,
    build: Chunk,
    probe_keys,
    build_keys,
    lo: int,
    size: int,
    join_type: str = INNER,
    payload=None,
):
    """Direct-addressing join for a unique build side whose (single) key
    range is bounded by catalog stats: build rows scatter into a dense
    row-lookup table indexed by key-lo, probes gather their match in O(1).

    Replaces sort+searchsorted (O(B log B) build + O(log B) per probe) with
    one unique-index scatter + one gather — the TPU-safe scatter shape
    (serialization only bites on DUPLICATE indices) and the CPU-fallback
    fast path. The reference's analog is the dense-key array join
    (be/src/exec/join_hash_map.h DirectMappingJoinHashMap).
    """
    payload = list(payload if payload is not None else build.schema.names)
    pk, p_ok, bk, b_ok = pack_key_pair(probe, build, probe_keys, build_keys)

    # dead/NULL build rows land in the spill slot (dropped)
    with phase("build"):
        idxb = jnp.where(b_ok, bk - lo, size)
        lut = jnp.full((size,), -1, jnp.int32).at[idxb].set(
            jnp.arange(build.capacity, dtype=jnp.int32), mode="drop"
        )
    with phase("probe"):
        idxp = pk - lo
        in_range = p_ok & (idxp >= 0) & (idxp < size)
        row = lut[jnp.clip(idxp, 0, size - 1)]
        match = in_range & (row >= 0)
        build_row = jnp.clip(row, 0, build.capacity - 1)
    return _unique_join_epilogue(
        probe, build, payload, match, build_row, join_type)


def hash_join_expand(
    probe: Chunk,
    build: Chunk,
    probe_keys,
    build_keys,
    out_capacity: int,
    join_type: str = INNER,
    payload=None,
    bit_widths=None,
    build_order=None,  # precomputed argsort of the packed build keys
):
    """General join allowing duplicate build keys.

    Expands matches by run-length: for probe row r matching build run
    [start_r, end_r), emits (r, start_r + j) pairs. Static out_capacity with
    true output size returned for host overflow handling.
    Returns (chunk, true_rows).
    """
    payload = list(payload if payload is not None else build.schema.names)
    pk, p_ok, bk, _b_ok = pack_key_pair(
        probe, build, probe_keys, build_keys, bit_widths
    )  # build NULL/dead rows pack to the sentinel

    with phase("build"):
        order = (build_order if build_order is not None
                 else jnp.argsort(bk, stable=True))
        bk_sorted = bk[order]
    bcap = build.capacity

    with phase("probe"):
        probe_ok = p_ok & (pk != _I64MAX)
        start = jnp.searchsorted(bk_sorted, pk, side="left")
        end = jnp.searchsorted(bk_sorted, pk, side="right")
        counts = jnp.where(probe_ok, end - start, 0)

    if join_type == LEFT_SEMI:
        out = probe.and_sel(counts > 0)
        return out, out.num_rows()
    if join_type == LEFT_ANTI:
        out = probe.and_sel(counts == 0)
        return out, out.num_rows()
    if join_type == LEFT_OUTER:
        counts = jnp.where(probe.sel_mask() & (counts == 0), 1, counts)
    elif join_type != INNER:
        raise NotImplementedError(join_type)

    with phase("expand"):
        total = jnp.sum(counts)
        # expansion: repeat probe-row ids by counts into fixed out_capacity
        probe_rows = jnp.repeat(
            jnp.arange(probe.capacity), counts,
            total_repeat_length=out_capacity
        )
        # offset of each output slot within its probe row's run
        run_start = jnp.cumsum(counts) - counts  # first out slot per probe row
        offs = jnp.arange(out_capacity) - run_start[probe_rows]
        build_pos = jnp.clip(start[probe_rows] + offs, 0, bcap - 1)
        build_row = order[build_pos]
        out_live = jnp.arange(out_capacity) < total
        if join_type == LEFT_OUTER:
            # probe_ok masking matters: a NULL-key probe row must not "match"
            # the build side's sentinel run (NULL/dead rows also pack to the
            # sentinel), so its payload stays NULL
            had_match = (probe_ok & ((end - start) > 0))[probe_rows]
        else:
            had_match = jnp.ones((out_capacity,), jnp.bool_)
        taken = probe.take(probe_rows)

    data = list(taken.data)
    valid = list(taken.valid)
    out_fields = _merge_schemas(probe, build, payload)
    with phase("payload"):
        for n in payload:
            i = build.schema.index(n)
            d = build.data[i][build_row]
            v = build.valid[i]
            v = None if v is None else v[build_row]
            if join_type == LEFT_OUTER:
                v = had_match if v is None else (v & had_match)
            data.append(d)
            valid.append(v)
    sel = out_live if taken.sel is None else (out_live & taken.sel)
    return Chunk(Schema(out_fields), tuple(data), tuple(valid), sel), total
