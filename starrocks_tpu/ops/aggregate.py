"""Sort-based grouped aggregation.

Reference behavior: be/src/exec/aggregator.h:255 + agg hash maps
(be/src/exec/aggregate/agg_hash_variant.h) — blocking hash aggregation with
two-phase (local partial / global final) splitting for distribution
(SURVEY §2.4 item 4). TPUs lack a scatter-friendly memory model, so instead
of a hash table we use: lexicographic multi-key sort -> segment boundaries ->
segment reductions. Group count has a *static capacity*; the operator returns
the true group count so the host executor can detect overflow and recompile
at a larger capacity (the adaptive-DOP analog).

Modes (for mesh two-phase aggregation):
- COMPLETE: raw rows in, final values out.
- PARTIAL:  raw rows in, merge-able state columns out (avg -> sum+count).
- FINAL:    state columns in (from PARTIAL, e.g. after an all_to_all
            exchange), final values out.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from .. import types as T
from ..column.column import Chunk, Field, Schema
from ..exprs.compile import EVal, ExprCompiler
from ..exprs.ir import AggExpr, Col, Expr
from .common import boundaries, eval_keys, key_sort_arrays, phase
from .segment import (
    _group_bounds_sorted, seg_first_index, seg_max, seg_min, seg_sums,
)


def _as_f64(a: EVal):
    """Arg data as float64 (decimals unscale)."""
    d = jnp.asarray(a.data)
    if a.type.is_decimal:
        return jnp.asarray(d, jnp.float64) / (10 ** a.type.scale)
    return jnp.asarray(d, jnp.float64)


def _read_state(cc, col_name, live_rows, reorder):
    st = cc.eval(Col(col_name))
    return jnp.where(live_rows, reorder(jnp.asarray(st.data)), 0)

COMPLETE = "complete"
PARTIAL = "partial"
FINAL = "final"


def _sum_out_type(t: T.LogicalType) -> T.LogicalType:
    if t.is_decimal128:
        return t
    if t.is_decimal:
        return T.DECIMAL(18, t.scale)
    if t.is_float:
        return T.DOUBLE
    if t.kind is T.TypeKind.BOOLEAN:
        return T.BIGINT
    return T.BIGINT


def _minmax_identity(t: T.LogicalType, is_min: bool):
    if t.is_float:
        return jnp.inf if is_min else -jnp.inf
    info = jnp.iinfo(t.dtype) if t.kind is not T.TypeKind.BOOLEAN else None
    if info is None:
        return True if is_min else False
    return info.max if is_min else info.min


# moment-sketch families: PARTIAL state = running sums of powers/products
# (the decomposable form of the reference's AggregateFunction state objects,
# be/src/exprs/agg/variance.h-style)
_VAR_FNS = {"var_pop", "var_samp", "stddev_pop", "stddev_samp"}
_COVAR_FNS = {"covar_pop", "covar_samp", "corr"}
# need the full value multiset -> cannot be split into partial/final
_HOLISTIC_FNS = {"percentile_cont", "percentile_disc", "array_agg",
                 # sketch aggregates run COMPLETE (the distributed planner
                 # gathers rows); a PARTIAL/FINAL register-merge split is a
                 # natural later step — registers are themselves mergeable
                 "approx_count_distinct", "hll_sketch", "hll_union",
                 "hll_union_agg", "bitmap_agg", "bitmap_union",
                 "bitmap_union_count", "intersect_count"}
_SKETCH_FNS = {"approx_count_distinct", "hll_sketch", "hll_union",
               "hll_union_agg", "bitmap_agg", "bitmap_union",
               "bitmap_union_count", "intersect_count"}


def decomposable(aggs: tuple) -> bool:
    """True when every aggregate supports the PARTIAL/FINAL two-phase split
    (drives the distributed planner's exchange strategy choice)."""
    return all(a.fn not in _HOLISTIC_FNS for _, a in aggs)


def _state_fields(name: str, agg: AggExpr, arg_t: Optional[T.LogicalType]):
    """State columns a PARTIAL aggregation emits for `agg` (name -> type)."""
    if agg.fn == "count" or agg.fn == "count_star":
        return [(f"{name}", T.BIGINT)]
    if agg.fn == "sum":
        return [(f"{name}", _sum_out_type(arg_t))]
    if agg.fn in ("min", "max"):
        return [(f"{name}", arg_t)]
    if agg.fn == "avg":
        return [(f"{name}__sum", _sum_out_type(arg_t)), (f"{name}__cnt", T.BIGINT)]
    if agg.fn in _VAR_FNS:
        return [(f"{name}__sum", T.DOUBLE), (f"{name}__ssq", T.DOUBLE),
                (f"{name}__cnt", T.BIGINT)]
    if agg.fn in _COVAR_FNS:
        return [(f"{name}__sx", T.DOUBLE), (f"{name}__sy", T.DOUBLE),
                (f"{name}__sxy", T.DOUBLE), (f"{name}__sxx", T.DOUBLE),
                (f"{name}__syy", T.DOUBLE), (f"{name}__cnt", T.BIGINT)]
    raise NotImplementedError(f"aggregate {agg.fn}")


def _key_domain(k) -> Optional[tuple]:
    """(base, lo) static value domain of one group key, or None when
    unbounded. Shared by the planner's capacity seeding (bounded_domain) and
    the runtime packed-gid path (_try_lowcard) so the two can never disagree
    about which keys are coverable."""
    if k.dict is not None:
        return max(len(k.dict), 1), 0
    if k.type.kind is T.TypeKind.BOOLEAN:
        return 2, 0
    if (k.bounds is not None
            and jnp.asarray(k.data).ndim == 1  # wide (ARRAY/DEC128) keys
            # can't pack: their bounds describe ELEMENTS, not the value
            and jnp.issubdtype(jnp.asarray(k.data).dtype, jnp.integer)):
        # stats-bounded integer/date domain (bounds propagate through the
        # expr compiler, e.g. extract(year FROM ...)): codes are value - lo
        lo, hi = int(k.bounds[0]), int(k.bounds[1])
        return hi - lo + 1, lo
    return None


def bounded_domain(chunk: Chunk, group_by) -> Optional[int]:
    """Static size of the group-key domain when every key is bounded
    (dict codes, booleans, stats-bounded ints) — planner uses it to seed the
    aggregation capacity so the sort-free packed-gid path covers dense
    high-cardinality keys (e.g. GROUP BY l_orderkey) too."""
    from ..runtime.config import config as _cfg

    if not group_by or not _cfg.get("enable_lowcard_agg"):
        # seeding a domain-sized capacity is only useful if _try_lowcard
        # will actually take it; otherwise the lexsort path would pay for
        # domain-many output slots
        return None
    keys = eval_keys(chunk, tuple(e for _, e in group_by))
    total = 1
    for k in keys:
        dom = _key_domain(k)
        if dom is None:
            return None
        total *= dom[0] + (1 if k.valid is not None else 0)
        if total > (1 << 26):  # give up early on huge domains
            return None
    return total


# chunks a compaction wrote, or larger. Not a width rule: below it an int32
# key wins too, but packing every key that fits moves the lowered text of
# the mesh's Q1 (digest `mesh_q1_f1`, a fragment program of the cell
# `tpch_sf10_x4.join`) and of one-chip Q3 at the tests' scale (`one_chip_q3`;
# tests/data/lowered_before_mesh_compaction.json), and the old cells'
# programs were not PR 32's to move. Whoever may move them deletes this
# constant and its test.
NARROW_SORT_KEY_ROWS = 1 << 13


def _mixed_radix_pack(keys, live, total_limit: int, out_dtype):
    """THE single mixed-radix key packer (null -> extra code past the
    domain, dead rows -> `total`, which sorts/indexes past every live
    code). Shared by the dense packed-gid path (int32, capacity-limited)
    and the packed sort-key path (int64, 2^62-limited) so the two can
    never disagree about group identity. Returns (packed, infos, total)
    or None when a key is unbounded or the product exceeds the limit."""
    infos = []
    total = 1
    for k in keys:
        dom = _key_domain(k)
        if dom is None:
            return None
        base, lo = dom
        has_null = k.valid is not None
        size = base + (1 if has_null else 0)
        infos.append((k, base, has_null, size, lo))
        total *= size
        if total > total_limit:
            return None
    packed = jnp.zeros((live.shape[0],), out_dtype)
    for k, base, has_null, size, lo in infos:
        code = jnp.clip(jnp.asarray(k.data, jnp.int64) - lo, 0, base - 1)
        code = jnp.asarray(code, out_dtype)
        if has_null:
            code = jnp.where(k.valid, code, base)
        packed = packed * size + code
    return jnp.where(live, packed, total), infos, total


def _packed_sort_codes(keys, live):
    """One mixed-radix code per row packing ALL bounded group keys (dead
    rows -> a sentinel that sorts last), or None when a key is unbounded or
    the domain product overflows 2^62. The sort-path agg then argsorts ONE
    key instead of lexsorting k arrays + validity masks — the multi-key
    comparator is the lexsort path's dominant cost (TPC-H Q16's 4-key
    distinct level, Q13's 2-key histogram). The code is an int32 where the
    domain fits one (SSB's year x brand: 7,000) and the chunk is large
    enough for the key's width to matter: a TPU sorts an int64 key as two
    u32 operands, and XLA took 81 s to compile that argsort at 901,120 rows
    against 35 s for the int32 one, and at 17,408 rows still ~45 s against
    ~12 (PR 32, v5e described). Under NARROW_SORT_KEY_ROWS, the floor of a
    compaction's output (`sql/physical.shrink_capacity`), a chunk's program
    stays as it was."""
    out = None
    if live.shape[0] >= NARROW_SORT_KEY_ROWS:
        out = _mixed_radix_pack(keys, live, (1 << 31) - 1, jnp.int32)
    out = out or _mixed_radix_pack(keys, live, 1 << 62, jnp.int64)
    return None if out is None else out[0]


def _try_lowcard(chunk, group_by, keys, live, num_groups: int, mode: str, aggs=()):
    """Sort-free fast path when every group key has a bounded domain
    (dictionary codes / booleans): group id = mixed-radix packed codes, and
    aggregates are direct segment reductions — no lexsort. This is the
    re-design of the reference's fixed-size SIMD agg hash maps
    (be/src/exec/aggregate/agg_hash_map.h) for TPU: the Q1/SSB-class
    low-cardinality group-bys skip the O(n log n) sort entirely.

    Returns (gid[cap] int32 with dead rows OUT of range, infos, total) or
    None when a key is unbounded or the domain exceeds num_groups."""
    from ..runtime.config import config as _cfg

    if mode == FINAL or not group_by or not _cfg.get("enable_lowcard_agg"):
        return None
    if any(a.fn == "array_agg" for _, a in aggs):
        # array_agg needs group-contiguous positions (the sort path)
        return None
    out = _mixed_radix_pack(keys, live, num_groups, jnp.int32)
    if out is None:
        return None
    gid, infos, total = out  # dead rows pack to `total`: out-of-range,
    return gid, infos, total  # dropped by the segment ops


def _lowcard_key_columns(infos, total: int, num_groups: int):
    """Decode slot ids back into per-key code columns (+ NULL validity)."""
    slots = jnp.arange(num_groups, dtype=jnp.int32)
    cols = []
    strides = []
    s = 1
    for k, base, has_null, size, lo in reversed(infos):
        strides.append(s)
        s *= size
    strides = list(reversed(strides))
    for (k, base, has_null, size, lo), stride in zip(infos, strides):
        code = (slots // stride) % size
        valid = None
        if has_null:
            valid = code != base
            code = jnp.where(valid, code, 0)
        cols.append((k, jnp.asarray(code + lo, k.type.dtype), valid))
    return cols



def _string_hash_lut(d):
    """Stable per-code 64-bit hashes of a StringDict's VALUES (FNV-1a over
    utf-8). Sketches built from different tables/dictionary rebuilds must
    agree on equal strings — hashing raw codes would make sketches
    non-mergeable and unions overcount. Cached on the dict (trace-time
    constant)."""
    import numpy as np

    cached = _HASH_LUTS.get(id(d))
    if cached is not None and cached[0] is d:
        return cached[1]
    n = max(len(d), 1)
    encoded = [str(v).encode() for v in d.values[:len(d)]]
    out = np.full(n, 0xCBF29CE484222325, dtype=np.uint64)
    if encoded and not any(b"\x00" in s for s in encoded):
        # vectorized FNV: fixed-width byte matrix (NUL-padded), fold
        # column-wise; the first zero byte ends the value, which is only
        # sound when no value embeds a NUL (checked above)
        m = np.array(encoded, dtype=bytes).view(np.uint8)
        m = m.reshape(len(encoded), -1) if m.size else np.zeros(
            (len(encoded), 1), np.uint8)
        alive = np.ones(n, dtype=bool)
        with np.errstate(over="ignore"):  # FNV-1a wraps mod 2^64 by design
            for j in range(m.shape[1]):
                b = m[:, j]
                alive = alive & (b != 0)
                folded = (out ^ b) * np.uint64(0x100000001B3)
                out = np.where(alive, folded, out)
    elif encoded:  # embedded NULs: exact scalar fold for those dicts
        with np.errstate(over="ignore"):
            for i, s in enumerate(encoded):
                h = np.uint64(0xCBF29CE484222325)
                for byte in s:
                    h = (h ^ np.uint64(byte)) * np.uint64(0x100000001B3)
                out[i] = h
    if len(_HASH_LUTS) > 64:
        _HASH_LUTS.clear()
    _HASH_LUTS[id(d)] = (d, out)  # strong ref keeps the id stable
    return out


_HASH_LUTS: dict = {}


def _hash_input_i64(a: EVal):
    """Distinct-preserving int64 view of a column for sketch hashing
    (strings hash their VALUE bytes via a dict LUT; floats hash their bit
    patterns)."""
    if a.type.is_wide:
        raise NotImplementedError(f"cannot sketch {a.type!r} values")
    if a.type.is_string and a.dict is not None:
        lut = jnp.asarray(_string_hash_lut(a.dict).view("int64"))
        codes = jnp.clip(jnp.asarray(a.data, jnp.int32), 0, lut.shape[0] - 1)
        return lut[codes]
    if a.type.is_float:
        return jax.lax.bitcast_convert_type(
            jnp.asarray(a.data, jnp.float64), jnp.int64)
    return jnp.asarray(a.data, jnp.int64)


def _emit_sketch_agg(cc, name, agg, cap, live_rows, reorder, gid,
                     num_groups):
    """HLL / BITMAP aggregate column (ops/sketch.py kernels)."""
    from . import sketch
    from ..runtime.config import config as _cfg

    from ..exprs.ir import Call as _Call

    fn = agg.fn
    arg = agg.arg
    if (fn in ("bitmap_agg", "bitmap_union", "bitmap_union_count")
            and isinstance(arg, _Call) and arg.fn == "to_bitmap"):
        # bitmap_union(to_bitmap(x)): skip the per-row plane materialization
        # and scatter x's values directly (the fused presence path)
        arg = arg.args[0]
    a = cc.eval(arg)
    m = live_rows if a.valid is None else (
        live_rows & reorder(jnp.broadcast_to(a.valid, (cap,))))

    if fn in ("approx_count_distinct", "hll_sketch"):
        p = _cfg.get("hll_precision")
        vals = reorder(jnp.broadcast_to(_hash_input_i64(a), (cap,)))
        regs = sketch.hll_registers_from_values(vals, m, gid, num_groups, p)
        if fn == "approx_count_distinct":
            return (Field(name, T.BIGINT, False),
                    sketch.hll_estimate(regs), None)
        return Field(name, T.HLL(p), False), regs, None

    if fn in ("hll_union", "hll_union_agg"):
        if not a.type.is_hll:
            raise TypeError(f"{fn} expects an HLL column, got {a.type!r}")
        d = jnp.where(m[:, None], reorder(jnp.asarray(a.data)), 0)
        regs = sketch.hll_union_registers(d, gid, num_groups)
        if fn == "hll_union_agg":
            return (Field(name, T.BIGINT, False),
                    sketch.hll_estimate(regs), None)
        return Field(name, a.type, False), regs, None

    if fn in ("bitmap_agg", "bitmap_union", "bitmap_union_count"):
        if a.type.is_bitmap:  # union of stored bitmaps: plane merge
            if fn == "bitmap_agg":
                raise TypeError("bitmap_agg expects integer values")
            d = jnp.where(m[:, None], reorder(jnp.asarray(a.data)), 0)
            planes = sketch.bitmap_union_planes(d, gid, num_groups)
            nb = a.type
        else:  # integer values: one fused presence scatter
            if not a.type.is_integer:
                raise TypeError(
                    f"{fn} expects BITMAP or integer values, got {a.type!r}")
            nbits = _cfg.get("bitmap_default_domain")
            if a.bounds is not None and a.bounds[1] is not None \
                    and 0 <= a.bounds[1] < (1 << 24):
                nbits = int(a.bounds[1]) + 1
            vals = reorder(jnp.broadcast_to(
                jnp.asarray(a.data, jnp.int64), (cap,)))
            planes = sketch.bitmap_union_from_values(
                vals, m, gid, num_groups, nbits)
            nb = T.BITMAP(nbits)
        if fn == "bitmap_union_count":
            return (Field(name, T.BIGINT, False),
                    sketch.bitmap_count(planes), None)
        return Field(name, nb, False), planes, None

    if fn == "intersect_count":
        if not a.type.is_bitmap:
            raise TypeError(
                f"intersect_count expects a BITMAP column, got {a.type!r}")
        dim_e, *lits = agg.extra
        d = jnp.where(m[:, None], reorder(jnp.asarray(a.data)), 0)
        acc = None
        for lit in lits:
            eqv = cc.call("eq", cc.eval(dim_e), cc.eval(lit))
            sel = jnp.broadcast_to(jnp.asarray(eqv.data, jnp.bool_), (cap,))
            if eqv.valid is not None:
                sel = sel & jnp.broadcast_to(eqv.valid, (cap,))
            mi = m & reorder(sel)
            planes = sketch.bitmap_union_planes(
                jnp.where(mi[:, None], d, 0), gid, num_groups)
            acc = planes if acc is None else sketch.bitmap_binary(
                acc, planes, "and")
        return Field(name, T.BIGINT, False), sketch.bitmap_count(acc), None

    raise NotImplementedError(fn)


def _emit_agg_columns(cc, aggs, mode, cap, live_rows, reorder, gid,
                      num_groups, indices_sorted, arr_cap=256,
                      aux_checks=None, extra_sums=(), sums_info=None,
                      packed_groups=None):
    """Emit aggregate output columns — shared by the sort path (reorder
    permutes rows into group order) and the low-cardinality packed-gid path
    (reorder is identity). live_rows is the row-liveness mask AFTER reorder.

    Every segment sum of the node runs in ONE `seg_sums` batch: each
    aggregate is a generator (`_agg_column`) that yields the sums it needs as
    `[(vals, nbits), ...]`, is sent their results once the batch has run, and
    returns its output columns `[(Field, data, valid), ...]`. `extra_sums`
    ride in the same batch (the packed-gid path's group count).
    `packed_groups`, on the packed-gid path: live rows' gids lie in [0,
    packed_groups) and dead rows' outside (the packed domain, often far
    under the capacity: Q1 has 6 of 1,024), so the sums run over that many
    groups, padded with empty ones, and a sum's operand need not be masked
    by liveness again. Returns (fields, data, valid, results of
    extra_sums); `sums_info`, when given, is filled with what the batch
    was (`seg_sums`)."""
    masked_args: dict = {}  # arg expr -> (EVal, its live-and-valid mask)
    sum_operands: dict = {}  # (arg expr, sum type) -> the masked values

    def live_and_valid(a):
        return live_rows if a.valid is None else (
            live_rows & reorder(jnp.broadcast_to(a.valid, (cap,))))

    def masked_arg(arg):
        """The argument and its row mask, one object an expression: equal
        operands of different aggregates (sum(x), avg(x), their nonempty
        masks) reach `seg_sums` as the same array and are summed once."""
        if arg not in masked_args:
            a = cc.eval(arg)
            masked_args[arg] = (a, live_and_valid(a))
        return masked_args[arg]

    def sum_operand(arg, out_t):
        key = (arg, out_t)
        if key not in sum_operands:
            a, m = masked_arg(arg)
            d = reorder(jnp.broadcast_to(_to_rep(a, out_t), (cap,)))
            # gid alone already drops the dead rows of the packed-gid path:
            # a second select would only keep XLA from reading the column
            # straight into the reduction (4 ms of Q1 at SF10)
            sum_operands[key] = d if (
                packed_groups is not None and m is live_rows
            ) else jnp.where(m, d, 0)
        return sum_operands[key]

    # a count's operand is a 0/1 mask: one limb. FINAL sums partial counts.
    cnt_bits = 1 if mode != FINAL else 64

    def _agg_column(name, agg):
        if agg.fn in ("count_star",) or (agg.fn == "count" and agg.arg is None):
            if mode == FINAL:
                st = cc.eval(Col(name))
                v = jnp.where(live_rows, reorder(jnp.asarray(st.data, jnp.int64)), 0)
                cnt, = yield [(v, 64)]
            else:
                cnt, = yield [(live_rows, 1)]
            return [(Field(name, T.BIGINT, False), cnt, None)]

        if agg.fn == "avg":
            if mode == FINAL:
                sv = cc.eval(Col(f"{name}__sum"))
                cv = cc.eval(Col(f"{name}__cnt"))
                sum_t = sv.type
                vals = jnp.where(live_rows, reorder(jnp.asarray(sv.data)), 0)
                cnts = jnp.where(live_rows, reorder(jnp.asarray(cv.data)), 0)
            else:
                a, cnts = masked_arg(agg.arg)
                sum_t = _sum_out_type(a.type)
                vals = sum_operand(agg.arg, sum_t)
            gsum, gcnt = yield [(vals, 64), (cnts, cnt_bits)]
            if mode == PARTIAL:
                return [(Field(f"{name}__sum", sum_t, False), gsum, None),
                        (Field(f"{name}__cnt", T.BIGINT, False), gcnt, None)]
            denom = jnp.maximum(gcnt, 1)
            if sum_t.is_decimal:
                res = jnp.asarray(gsum, jnp.float64) / (10 ** sum_t.scale) / denom
            else:
                res = jnp.asarray(gsum, jnp.float64) / denom
            return [(Field(name, T.DOUBLE, True), res, gcnt > 0)]

        if agg.fn in _VAR_FNS:
            if mode == FINAL:
                s1 = _read_state(cc, f"{name}__sum", live_rows, reorder)
                s2 = _read_state(cc, f"{name}__ssq", live_rows, reorder)
                cnts = _read_state(cc, f"{name}__cnt", live_rows, reorder)
            else:
                a, cnts = masked_arg(agg.arg)
                d = reorder(jnp.broadcast_to(_as_f64(a), (cap,)))
                s1 = jnp.where(cnts, d, 0.0)
                s2 = jnp.where(cnts, d * d, 0.0)
            gs1, gs2, gn = yield [(s1, 64), (s2, 64), (cnts, cnt_bits)]
            if mode == PARTIAL:
                return [(Field(f"{name}__sum", T.DOUBLE, False), gs1, None),
                        (Field(f"{name}__ssq", T.DOUBLE, False), gs2, None),
                        (Field(f"{name}__cnt", T.BIGINT, False), gn, None)]
            samp = agg.fn.endswith("_samp")
            denom = jnp.maximum(gn - (1 if samp else 0), 1)
            var = jnp.maximum(
                (gs2 - gs1 * gs1 / jnp.maximum(gn, 1)) / denom, 0.0)
            res = jnp.sqrt(var) if agg.fn.startswith("stddev") else var
            return [(Field(name, T.DOUBLE, True), res,
                     gn > (1 if samp else 0))]

        if agg.fn in _COVAR_FNS:
            suffixes = ("sx", "sy", "sxy", "sxx", "syy")
            if mode == FINAL:
                moments = [_read_state(cc, f"{name}__{sfx}", live_rows, reorder)
                           for sfx in suffixes]
                cnts = _read_state(cc, f"{name}__cnt", live_rows, reorder)
            else:
                ax = cc.eval(agg.arg)
                ay = cc.eval(agg.extra[0])
                dx = reorder(jnp.broadcast_to(_as_f64(ax), (cap,)))
                dy = reorder(jnp.broadcast_to(_as_f64(ay), (cap,)))
                cnts = live_rows
                for v in (ax.valid, ay.valid):
                    if v is not None:
                        cnts = cnts & reorder(jnp.broadcast_to(v, (cap,)))
                moments = [jnp.where(cnts, d, 0.0)
                           for d in (dx, dy, dx * dy, dx * dx, dy * dy)]
            *sums, gn = yield [(d, 64) for d in moments] + [(cnts, cnt_bits)]
            if mode == PARTIAL:
                return [(Field(f"{name}__{sfx}", T.DOUBLE, False), dat, None)
                        for sfx, dat in zip(suffixes, sums)] + [
                            (Field(f"{name}__cnt", T.BIGINT, False), gn, None)]
            gx, gy, gxy, gxx, gyy = sums
            nf = jnp.maximum(gn, 1)
            if agg.fn == "corr":
                num = gn * gxy - gx * gy
                den2 = (gn * gxx - gx * gx) * (gn * gyy - gy * gy)
                den = jnp.sqrt(jnp.maximum(den2, 0.0))
                res = num / jnp.where(den > 0, den, 1.0)
                ok = (gn > 0) & (den > 0)
            else:
                cov = gxy - gx * gy / nf
                if agg.fn == "covar_samp":
                    res = cov / jnp.maximum(gn - 1, 1)
                    ok = gn > 1
                else:
                    res = cov / nf
                    ok = gn > 0
            return [(Field(name, T.DOUBLE, True), res, ok)]

        if agg.fn in _SKETCH_FNS:
            if mode != COMPLETE:
                raise NotImplementedError(
                    f"{agg.fn} cannot be split into partial/final")
            yield []
            return [_emit_sketch_agg(cc, name, agg, cap, live_rows,
                                     reorder, gid, num_groups)]

        if agg.fn in _HOLISTIC_FNS and agg.fn != "array_agg":
            if mode != COMPLETE:
                raise NotImplementedError(
                    f"{agg.fn} cannot be split into partial/final")
            yield []
            a = cc.eval(agg.arg)
            assert not a.type.is_string, f"{agg.fn} over strings"
            frac = float(agg.extra[0].value)
            d = reorder(jnp.broadcast_to(jnp.asarray(a.data), (cap,)))
            m = live_and_valid(a)
            gidm = jnp.where(m, jnp.asarray(gid, jnp.int32), num_groups)
            order2 = jnp.lexsort((d, gidm))
            g2 = gidm[order2]
            v2 = d[order2]
            left, right = _group_bounds_sorted(g2, num_groups)
            cnt = right - left
            ok = cnt > 0
            if agg.fn == "percentile_cont":
                vf = (jnp.asarray(v2, jnp.float64) / (10 ** a.type.scale)
                      if a.type.is_decimal else jnp.asarray(v2, jnp.float64))
                fpos = frac * jnp.asarray(cnt - 1, jnp.float64)
                lo = jnp.clip(jnp.floor(fpos).astype(jnp.int64), 0, None)
                hi = jnp.clip(jnp.ceil(fpos).astype(jnp.int64), 0, None)
                t = fpos - lo
                vlo = vf[jnp.clip(left + lo, 0, cap - 1)]
                vhi = vf[jnp.clip(left + hi, 0, cap - 1)]
                return [(Field(name, T.DOUBLE, True),
                         vlo * (1 - t) + vhi * t, ok)]
            # percentile_disc: smallest value with cum_dist >= frac
            k = jnp.clip(
                jnp.ceil(frac * jnp.asarray(cnt, jnp.float64)).astype(
                    jnp.int64) - 1, 0, jnp.maximum(cnt - 1, 0))
            res = v2[jnp.clip(left + k, 0, cap - 1)]
            return [(Field(name, a.type, True, a.dict), res, ok)]

        # sum / min / max / count(x)
        a, m = (masked_arg(agg.arg) if mode != FINAL
                else masked_arg(Col(name)))
        if a.type.is_decimal128 and agg.fn in ("min", "max"):
            # lexicographic limb refinement: per limb (ms->ls), keep only
            # rows still tied on all more-significant limbs and take the
            # segment extreme — 4 scatter-free passes
            from . import dec128 as d128

            is_min = agg.fn == "min"
            d = reorder(jnp.asarray(a.data))
            adj = d128.cmp_limbs(d)
            ident = (1 << 32) if is_min else -1
            gidc = jnp.clip(jnp.asarray(gid, jnp.int32), 0, num_groups - 1)
            segfn = seg_min if is_min else seg_max
            tied = m
            best_limbs = []
            for limb in adj:
                lv = jnp.where(tied, limb, ident)
                best = segfn(lv, gid, num_groups, identity=ident,
                             sorted_gid=indices_sorted)
                best_limbs.append(best)
                tied = tied & (limb == best[gidc])
            best_limbs[0] = best_limbs[0] ^ 0x80000000  # undo sign adjust
            res = jnp.stack([jnp.asarray(x, jnp.int64) & 0xFFFFFFFF
                             for x in best_limbs], axis=1)
            live_cnt, = yield [(m, 1)]
            return [(Field(name, a.type, True), res, live_cnt > 0)]
        if a.type.is_decimal128 and agg.fn not in ("sum", "count"):
            raise NotImplementedError(
                f"{agg.fn} over DECIMAL(>18) is not supported yet "
                "(sum/count/avg-via-sum are; cast to DOUBLE for the rest)")

        if agg.fn == "count":
            if mode == FINAL:
                vals = jnp.where(m, reorder(jnp.asarray(a.data, jnp.int64)), 0)
                res, = yield [(vals, 64)]
            else:
                res, = yield [(m, 1)]
            return [(Field(name, T.BIGINT, False), res, None)]
        if agg.fn == "sum" and a.type.is_decimal128:
            # 128-bit exact sum: per-32-bit-limb segment sums (limb sums of
            # up to 2^31 rows fit int64), then one device carry-propagation
            # pass; wraps mod 2^128 like the reference's int128 accumulator
            d = reorder(jnp.asarray(a.data))  # [cap, 4] limbs, ms first
            *limb_sums, live_cnt = yield [
                (jnp.where(m, d[:, i] & 0xFFFFFFFF, 0), 32)
                for i in range(4)] + [(m, 1)]
            out_limbs = [None] * 4
            carry = jnp.zeros_like(limb_sums[0])
            for i in (3, 2, 1, 0):  # least significant first
                tot = limb_sums[i] + carry
                out_limbs[i] = tot & 0xFFFFFFFF
                carry = tot >> 32
            return [(Field(name, a.type, True), jnp.stack(out_limbs, axis=1),
                     live_cnt > 0)]
        if agg.fn == "sum":
            out_t = a.type if mode == FINAL else _sum_out_type(a.type)
            vals = sum_operand(Col(name) if mode == FINAL else agg.arg, out_t)
            res, live_cnt = yield [(vals, 64), (m, 1)]
            return [(Field(name, out_t, True), res, live_cnt > 0)]
        if agg.fn in ("min", "max"):
            is_min = agg.fn == "min"
            ident = _minmax_identity(a.type, is_min)
            d = reorder(jnp.broadcast_to(jnp.asarray(a.data), (cap,)))
            dd = jnp.where(m, d, jnp.asarray(ident, a.type.dtype))
            segfn = seg_min if is_min else seg_max
            res = segfn(dd, gid, num_groups, identity=ident,
                        sorted_gid=indices_sorted)
            live_cnt, = yield [(m, 1)]
            return [(Field(name, a.type, True, a.dict), res, live_cnt > 0)]
        if agg.fn == "array_agg":
            if not indices_sorted:
                raise NotImplementedError(
                    "array_agg requires the sorted aggregation path")
            # rows are group-contiguous: position within group = row index -
            # group start; scatter (gid, pos) -> [G, K+1] (unique indices,
            # TPU-fast); K adapts via the aux overflow check
            d = reorder(jnp.broadcast_to(jnp.asarray(a.data), (cap,)))
            left = seg_first_index(gid, num_groups, cap)
            pos = jnp.arange(cap) - left[jnp.clip(gid, 0, num_groups - 1)]
            ok = m & (pos >= 0) & (pos < arr_cap)
            gi = jnp.where(ok, gid, num_groups)
            pi = jnp.where(ok, pos, 0)
            mat = jnp.zeros((num_groups + 1, arr_cap + 1), d.dtype)
            mat = mat.at[gi, 1 + pi].set(d, mode="drop")
            counts, = yield [(m, 1)]
            if aux_checks is not None:
                aux_checks["array_agg_max"] = jnp.max(
                    jnp.concatenate([counts, jnp.zeros(1, counts.dtype)]))
            mat = mat.at[:num_groups, 0].set(
                jnp.asarray(jnp.minimum(counts, arr_cap), d.dtype))
            return [(Field(name, T.ARRAY(a.type), True, a.dict),
                     mat[:num_groups], counts > 0)]
        raise NotImplementedError(f"aggregate {agg.fn}")

    def finish(column, results):
        try:
            column.send(results)
        except StopIteration as done:
            return done.value
        raise AssertionError("an aggregate asks for its sums once")

    columns = [_agg_column(name, agg) for name, agg in aggs]
    wanted = [next(col) for col in columns] + [list(extra_sums)]
    sum_groups = num_groups if packed_groups is None else packed_groups
    sums = (jnp.pad(r, (0, num_groups - sum_groups)) for r in seg_sums(
        [w for want in wanted for w in want], gid, sum_groups,
        sorted_gid=indices_sorted, info=sums_info))
    got = [[next(sums) for _ in want] for want in wanted]
    out = [c for col, res in zip(columns, got) for c in finish(col, res)]
    return ([f for f, _, _ in out], [d for _, d, _ in out],
            [v for _, _, v in out], got[-1])


def hash_aggregate(
    chunk: Chunk,
    group_by: tuple,  # tuple[(name, Expr)]
    aggs: tuple,  # tuple[(name, AggExpr)]
    num_groups: int,
    mode: str = COMPLETE,
    arr_cap: int = 256,
    aux_checks: dict | None = None,
    sums_info: dict | None = None,
):
    """Returns (output_chunk, true_group_count). Output capacity=num_groups.

    In FINAL mode, `aggs` args must be Cols referring to the PARTIAL state
    columns produced by the same spec (avg reads name__sum / name__cnt).
    `sums_info`, when given, is filled at trace time with what the node's
    batch of segment sums was (`ops/segment.seg_sums`).
    """
    cc = ExprCompiler(chunk)
    cap = chunk.capacity
    live = chunk.sel_mask()
    keys = eval_keys(chunk, tuple(e for _, e in group_by))

    lowcard = _try_lowcard(chunk, group_by, keys, live, num_groups, mode, aggs)
    if lowcard is not None:
        return _aggregate_with_gid(
            chunk, cc, group_by, aggs, num_groups, mode, *lowcard, live=live,
            sums_info=sums_info,
        )

    out_fields, out_data, out_valid = [], [], []

    if keys:
        packed = _packed_sort_codes(keys, live)
        if packed is not None:
            # stable single-key argsort: within-group row order matches the
            # lexsort path's, so float accumulation order (and thus exact
            # results) is identical
            with phase("lexsort"):
                order = jnp.argsort(packed)
            pk_s = packed[order]
            live_s = live[order]
            prev = jnp.concatenate(
                [jnp.full((1,), -1, pk_s.dtype), pk_s[:-1]])
            is_new = live_s & (pk_s != prev)
        else:
            with phase("lexsort"):
                order = jnp.lexsort(tuple(key_sort_arrays(keys, live)))
            is_new = boundaries(keys, live, order)
            live_s = live[order]
        gid = jnp.clip(jnp.cumsum(is_new) - 1, 0, num_groups - 1)
        ngroups = jnp.sum(is_new, dtype=jnp.int64)
        reorder = lambda x: x[order]  # noqa: E731

        # --- group key columns ------------------------------------------------
        first_pos = seg_first_index(gid, num_groups, cap)
        safe_first = jnp.clip(first_pos, 0, cap - 1)
        for (kname, _), k in zip(group_by, keys):
            ks = k.data[order][safe_first]
            kv = None if k.valid is None else k.valid[order][safe_first]
            out_fields.append(Field(kname, k.type, k.valid is not None, k.dict,
                                    bounds=k.bounds))
            out_data.append(ks)
            out_valid.append(kv)
    else:
        # global aggregation: one group holding all live rows. No sort, no
        # cumsum, no row permutation — each aggregate collapses to ONE fused
        # masked reduction over the chunk (seg_* have a num_groups==1 fast
        # path), which is the cheapest possible formulation on any backend.
        gid = jnp.zeros((cap,), jnp.int32)
        live_s = live
        # a global agg always yields one row (COUNT over empty set = 0)
        ngroups = jnp.asarray(1, jnp.int64)
        reorder = lambda x: x  # noqa: E731

    # --- aggregate columns ----------------------------------------------------
    agg_fields, agg_data, agg_valid, _ = _emit_agg_columns(
        cc, aggs, mode, cap, live_s, reorder, gid, num_groups,
        indices_sorted=True, arr_cap=arr_cap, aux_checks=aux_checks,
        sums_info=sums_info,
    )
    out_fields += agg_fields
    out_data += agg_data
    out_valid += agg_valid

    sel = jnp.arange(num_groups) < ngroups
    out = Chunk(Schema(tuple(out_fields)), tuple(out_data), tuple(out_valid), sel)
    return out, ngroups


def _to_rep(a: EVal, out_t: T.LogicalType):
    """Cast an arg EVal's data to the aggregation accumulator representation."""
    if a.type.is_decimal and out_t.is_decimal:
        d = jnp.asarray(a.data, jnp.int64)
        if a.type.scale < out_t.scale:
            d = d * (10 ** (out_t.scale - a.type.scale))
        return d
    if out_t.is_decimal and not a.type.is_decimal:
        return jnp.asarray(a.data, jnp.int64) * (10 ** out_t.scale)
    return jnp.asarray(a.data, out_t.dtype)


def final_agg_exprs(aggs: tuple) -> tuple:
    """Rewrite agg specs for the FINAL stage over PARTIAL state columns."""
    out = []
    for name, agg in aggs:
        if agg.fn in ("count", "count_star"):
            out.append((name, AggExpr("count", Col(name))))
        elif agg.fn == "sum":
            out.append((name, AggExpr("sum", Col(name))))
        elif agg.fn == "min":
            out.append((name, AggExpr("min", Col(name))))
        elif agg.fn == "max":
            out.append((name, AggExpr("max", Col(name))))
        elif agg.fn == "avg":
            out.append((name, AggExpr("avg", None)))
        elif agg.fn in _VAR_FNS or agg.fn in _COVAR_FNS:
            out.append((name, AggExpr(agg.fn, None)))
        else:
            raise NotImplementedError(agg.fn)
    return tuple(out)


def _aggregate_with_gid(chunk, cc, group_by, aggs, num_groups, mode,
                        gid, infos, total, live, sums_info=None):
    """Aggregate via direct (unsorted) segment reductions over packed gids."""
    cap = chunk.capacity

    out_fields, out_data, out_valid = [], [], []
    for (name, _), (k, code, kvalid) in zip(
        group_by, _lowcard_key_columns(infos, total, num_groups)
    ):
        out_fields.append(Field(name, k.type, kvalid is not None, k.dict,
                                bounds=k.bounds))
        out_data.append(code)
        out_valid.append(kvalid)

    agg_fields, agg_data, agg_valid, (group_count,) = _emit_agg_columns(
        cc, aggs, mode, cap, live, lambda x: x, gid, num_groups,
        indices_sorted=False, extra_sums=((live, 1),), sums_info=sums_info,
        packed_groups=total,
    )
    out_fields += agg_fields
    out_data += agg_data
    out_valid += agg_valid

    in_domain = jnp.arange(num_groups) < total
    sel = in_domain & (group_count > 0)
    ngroups = jnp.sum(sel, dtype=jnp.int64)
    out = Chunk(Schema(tuple(out_fields)), tuple(out_data), tuple(out_valid), sel)
    return out, ngroups
