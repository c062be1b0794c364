"""Window (analytic) functions.

Reference behavior: be/src/exec/analytor.h:54 + analytic_node — partitioned,
frame-based analytic evaluation. TPU re-design: one lexsort by
(partition keys, order keys), segment ids from partition boundaries, then
- whole-partition aggregates  = segment reduction gathered back per row,
- running aggregates (default RANGE UNBOUNDED PRECEDING..CURRENT ROW frame
  with peers) = segmented cumulative sums with peer-group correction,
- row_number / rank / dense_rank = positional arithmetic on the sorted order.
The output chunk is in sorted order (SQL leaves intermediate order
unspecified); new columns align with it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import types as T
from ..column.column import Chunk, Field
from ..exprs.compile import ExprCompiler
from .common import boundaries, eval_keys, phase
from .sort import _descending


def window_topn_prefilter_safe(funcs, limit_spec) -> bool:
    """Whether dropping rows BEFORE the window's sort is sound for this
    function set. The threshold is the per-partition k-th ROW's score, so

    - the limited function must count rows: rank()/row_number(). dense_rank
      counts DISTINCT order keys, so its k-th rank can sit past the k-th
      row (scores [10,10,9]: dense_rank 2 is the 9-row, but the 2nd row's
      score is 10 — the threshold would drop it);
    - every co-resident function (the analyzer merges all funcs sharing a
      (partition, order) spec into one LWindow) must read only the sorted
      prefix up to the current row's peer group. rank-like functions do;
      lead/last_value/nth_value and frames reaching FOLLOWING would be
      computed over the pruned subset and go wrong on surviving rows.

    The in-window limit_rank mask is exact for every function, so unsafe
    shapes simply skip the prefilter, not the rewrite."""
    limited = next((f[1] for f in funcs if f[0] == limit_spec[0]), None)
    if limited not in ("rank", "row_number"):
        return False
    return all(f[1] in ("rank", "row_number", "dense_rank") for f in funcs)


def window_topn_prefilter(chunk: Chunk, partition_by, order_by, k: int,
                          max_domain: int = 1024,
                          max_cells: int = 1 << 25):
    """Branch-free TopN runtime filter applied BEFORE the window's sort
    (the reference feeds the heap TopN's current threshold back into
    upstream operators; here the k-th key per partition becomes a mask).

    Requirements: a single order key and a bounded partition-key domain D
    (dict codes / bools / stats-bounded ints, the same _key_domain
    discipline as every other packing decision). Builds a [D, cap] masked
    score matrix, takes each partition's k-th best via lax.top_k, and
    keeps rows scoring >= their partition's threshold — a superset of the
    rank() <= k row set (ties at the threshold stay, so the in-window
    rank mask still applies; callers gate on window_topn_prefilter_safe —
    the threshold is row-counting and prefix-only). NULL keys score the
    ceiling (NULLS FIRST:
    the null peer group ranks 1, occupying top threshold slots) or the
    floor (NULLS LAST: kept only while the partition has fewer than k
    scored rows). Returns (keep_mask, seed_rows) — seed_rows is a
    capacity seed for compacting the kept set (k per partition, with
    slack) — or None.
    """
    if k < 1 or len(order_by) != 1:
        return None
    expr, asc, nulls_first = order_by[0]
    live = chunk.sel_mask()
    cap = chunk.capacity
    (okey,) = eval_keys(chunk, (expr,))
    d = jnp.asarray(okey.data)
    if d.ndim != 1:
        return None  # wide (DECIMAL128/ARRAY) order keys
    if d.dtype == jnp.bool_:
        d = jnp.asarray(d, jnp.int8)
    # score: bigger = earlier rank
    score = d if not asc else _descending(d)
    if jnp.issubdtype(score.dtype, jnp.floating):
        floor, ceil = -jnp.inf, jnp.inf
    else:
        score = jnp.asarray(score, jnp.int64)
        floor = jnp.iinfo(jnp.int64).min
        ceil = jnp.iinfo(jnp.int64).max
    if okey.valid is not None:
        score = jnp.where(okey.valid, score,
                          ceil if nulls_first else floor)
    score = jnp.where(live, score, floor)
    if jnp.issubdtype(score.dtype, jnp.floating):
        # NaN order keys: the engine's sort (argsort/lexsort; DESC via
        # negation, which keeps NaN NaN) places them last in either
        # direction, so they rank worst — score them the floor. Raw NaN
        # would fail `>= kth` unconditionally (dropping NaN rows even in
        # partitions with fewer than k rows), and k NaNs in one partition
        # would make kth itself NaN, dropping the whole partition.
        score = jnp.where(jnp.isnan(score), floor, score)

    if partition_by:
        from .aggregate import _mixed_radix_pack

        pkeys = eval_keys(chunk, tuple(partition_by))
        packed = _mixed_radix_pack(pkeys, live, max_domain, jnp.int64)
        if packed is None:
            return None
        gid, _, total = packed
        D = int(total)
    else:
        gid = jnp.zeros((cap,), jnp.int64)
        D = 1
    if D * cap > max_cells:
        return None
    kk = min(k, cap)
    gidc = jnp.clip(gid, 0, D - 1)
    # the [D, cap] masked-compare matrix is the usual one-hot trick; the
    # threshold is exact (ties at the k-th key stay: `>=`)
    mat = jnp.where(
        jnp.arange(D, dtype=gid.dtype)[:, None] == gid[None, :],
        score[None, :], floor,
    )
    kth = jax.lax.top_k(mat, kk)[0][:, -1]  # [D] per-partition k-th
    keep = live & (score >= kth[gidc])
    # ties at the threshold can keep more than k rows a partition; the
    # overflow check covers them
    return keep, (kk + 8) * (D + 1)


def _seg_cummax_from_flags(vals, is_new):
    """Segmented 'value at segment start' propagation: for each row, the most
    recent value at a row where is_new was True (inclusive)."""
    idx = jnp.where(is_new, jnp.arange(vals.shape[0]), 0)
    start_idx = jax.lax.associative_scan(jnp.maximum, idx)
    return vals[start_idx], start_idx


def window_op(
    chunk: Chunk,
    partition_by: tuple,  # tuple[Expr]
    order_by: tuple,  # tuple[(Expr, asc, nulls_first)]
    funcs: tuple,  # tuple[(out_name, fn, arg|None, offset, default)]
    limit_spec: tuple | None = None,  # (rank-func out_name, k): see below
    counters: dict | None = None,
) -> Chunk:
    """limit_spec marks a per-partition segmented top-N: only rows whose
    named rank()/row_number()/dense_rank() value is <= k stay selected in
    the output (the optimizer plants it from a `rk <= k` filter — the TopN
    runtime-filter analog; downstream operators then see ~k*partitions
    live rows instead of the whole window input)."""
    cap = chunk.capacity
    live = chunk.sel_mask()
    pkeys = eval_keys(chunk, partition_by)
    okeys = eval_keys(chunk, tuple(e for e, _, _ in order_by))

    # sort: dead last, then partition keys, then order keys. Packing tries
    # the FULL key tuple first (one argsort), then just the partition keys
    # (partition prefix + liveness fold into one operand, order keys stay
    # lexsort operands), then the all-operand lexsort.
    from .sort import packed_order_key

    pspecs = [(None, True, False)] * len(pkeys)  # partitions: asc, nulls last
    packed = packed_order_key(
        pkeys + okeys, pspecs + list(order_by), live)
    if packed is not None:
        with phase("sort"):
            order = jnp.argsort(packed, stable=True)
    else:
        ops = []
        for k, (_, asc, nulls_first) in zip(reversed(okeys), reversed(list(order_by))):
            d = k.data
            if d.dtype == jnp.bool_:
                d = jnp.asarray(d, jnp.int8)
            ops.append(d if asc else _descending(d))
            if k.valid is not None:
                ops.append(jnp.asarray(k.valid if nulls_first else ~k.valid, jnp.int8))
        ppacked = packed_order_key(pkeys, pspecs, live) if pkeys else None
        if ppacked is not None:
            ops.append(ppacked)  # partition prefix + live fold into one
        else:
            for k in reversed(pkeys):
                ops.append(k.data)
                if k.valid is not None:
                    ops.append(jnp.asarray(~k.valid, jnp.int8))
            ops.append(jnp.asarray(~live, jnp.int8))
        with phase("sort"):
            order = jnp.lexsort(tuple(ops))

    sorted_chunk = chunk.take(order)
    live_s = live[order]
    pos = jnp.arange(cap)

    if pkeys:
        part_new = boundaries(pkeys, live, order)
    else:
        part_new = jnp.zeros((cap,), jnp.bool_).at[0].set(jnp.any(live))
    # peer groups: rows equal on partition+order keys
    peer_new = boundaries(pkeys + okeys, live, order) if okeys else part_new

    part_start, _ = _seg_cummax_from_flags(pos, part_new)
    row_in_part = pos - part_start
    # "end" searches must stop at the live/dead boundary: treat the first
    # dead row as a segment start so indices never land on padding
    dead_start = ~live_s
    end_peer_flags = peer_new | part_new | dead_start
    end_part_flags = part_new | dead_start
    peer_start, _ = _seg_cummax_from_flags(pos, peer_new | part_new)
    _nxt_peer = jnp.concatenate([end_peer_flags[1:], jnp.ones((1,), jnp.bool_)])
    peer_end = _carry_scan(pos[::-1], _nxt_peer[::-1])[::-1]
    _nxt_part = jnp.concatenate([end_part_flags[1:], jnp.ones((1,), jnp.bool_)])
    part_end = _carry_scan(pos[::-1], _nxt_part[::-1])[::-1]

    def frame_bounds(frame):
        """Per-row inclusive [start, end] positions of an explicit frame in
        the sorted order, clamped to the row's partition. start > end means
        an empty frame. Reference frame semantics: be/src/exec/analytor.h:54."""
        mode, st, so, et, eo = frame
        if mode == "rows":
            start = {"up": part_start, "p": pos - int(so or 0), "cr": pos,
                     "f": pos + int(so or 0)}[st]
            end = {"p": pos - int(eo or 0), "cr": pos, "f": pos + int(eo or 0),
                   "uf": part_end}[et]
        else:  # RANGE: CURRENT ROW = the whole peer group
            start = {"up": part_start, "cr": peer_start}.get(st)
            end = {"cr": peer_end, "uf": part_end}.get(et)
            if start is None or end is None:
                k = okeys[0]
                if k.dict is not None:
                    raise NotImplementedError(
                        "RANGE frame offsets require a numeric ORDER BY key")
                # offsets are user-unit; decimal keys are scaled-int reps
                unit = 10 ** k.type.scale if k.type.is_decimal else 1
                so = None if so is None else so * unit
                eo = None if eo is None else eo * unit
                asc = order_by[0][1]
                nf = order_by[0][2]
                ks = jnp.asarray(jnp.asarray(k.data)[order], jnp.float64)
                if k.valid is not None:
                    kv = jnp.asarray(k.valid)[order]
                    # nulls sort as a block at one end; pin them to the
                    # matching sentinel so the partition stays monotone
                    at_min = nf if asc else not nf
                    ks = jnp.where(kv, ks, -jnp.inf if at_min else jnp.inf)
                else:
                    kv = jnp.ones((cap,), jnp.bool_)
                iters = cap.bit_length() + 1
                hi0 = part_end + 1
                if start is None:
                    sgn = -1.0 if st == "p" else 1.0
                    t = ks + (sgn * float(so) if asc else -sgn * float(so))
                    cmp = (lambda a, b: a >= b) if asc else (lambda a, b: a <= b)
                    start = _bsearch_first(ks, part_start, hi0, t, cmp, iters)
                    start = jnp.where(kv, start, peer_start)
                if end is None:
                    sgn = -1.0 if et == "p" else 1.0
                    t = ks + (sgn * float(eo) if asc else -sgn * float(eo))
                    cmp = (lambda a, b: a > b) if asc else (lambda a, b: a < b)
                    end = _bsearch_first(ks, part_start, hi0, t, cmp, iters) - 1
                    end = jnp.where(kv, end, peer_end)
        start = jnp.maximum(start, part_start)
        end = jnp.minimum(end, part_end)
        # detect emptiness BEFORE clamping into gather range (a frame wholly
        # outside its partition must stay empty); encode empty as (1, 0)
        empty = (start > end) | ~live_s
        start = jnp.clip(start, 0, cap - 1)
        end = jnp.clip(end, 0, cap - 1)
        return jnp.where(empty, 1, start), jnp.where(empty, 0, end)

    cc = ExprCompiler(sorted_chunk)
    new_fields, new_data, new_valid = [], [], []
    limit_rank = None  # the named rank column when limit_spec applies
    for spec in funcs:
        out_name, fn, arg, f_offset, f_default, *_rest = spec
        f_frame = _rest[0] if _rest else None
        if fn == "row_number":
            r = row_in_part + 1
            if limit_spec is not None and out_name == limit_spec[0]:
                limit_rank = r
            new_fields.append(Field(out_name, T.BIGINT, False))
            new_data.append(r)
            new_valid.append(None)
            continue
        if fn in ("rank", "dense_rank"):
            if fn == "rank":
                r = peer_start - part_start + 1
            else:
                in_part_newpeer = (peer_new | part_new) & ~part_new
                dr = jnp.cumsum(jnp.asarray(in_part_newpeer, jnp.int64))
                dr_at_start, _ = _seg_cummax_from_flags(dr, part_new)
                r = dr - dr_at_start + 1
            if limit_spec is not None and out_name == limit_spec[0]:
                limit_rank = r
            new_fields.append(Field(out_name, T.BIGINT, False))
            new_data.append(r)
            new_valid.append(None)
            continue

        if fn in ("lead", "lag"):
            v = cc.eval(arg)
            shift = -f_offset if fn == "lead" else f_offset
            d = jnp.broadcast_to(jnp.asarray(v.data), (cap,))
            val = jnp.roll(d, shift)
            vv = (jnp.broadcast_to(v.valid, (cap,)) if v.valid is not None
                  else jnp.ones((cap,), jnp.bool_))
            vv = jnp.roll(vv, shift)
            # rows whose source falls outside the partition -> NULL
            src = pos - shift
            in_bounds = (src >= 0) & (src < cap)
            src_c = jnp.clip(src, 0, cap - 1)
            same_part = part_start == jnp.where(in_bounds, part_start[src_c], -1)
            src_live = jnp.where(in_bounds, live_s[src_c], False)
            ok = in_bounds & same_part & live_s & src_live
            if f_default is not None:
                # out-of-partition slots take the declared default
                from ..exprs.compile import _infer_lit

                hv, _ = _infer_lit(f_default, v.type)
                val = jnp.where(ok, val, jnp.asarray(hv, val.dtype))
                new_valid.append(jnp.where(ok, vv, True))
            else:
                new_valid.append(vv & ok)
            new_fields.append(Field(out_name, v.type, True, v.dict))
            new_data.append(val)
            continue
        if fn in ("first_value", "last_value"):
            v = cc.eval(arg)
            d = jnp.broadcast_to(jnp.asarray(v.data), (cap,))
            if f_frame is not None:
                starts, ends = frame_bounds(f_frame)
                idx = starts if fn == "first_value" else ends
                empty = starts > ends
                vv = (jnp.broadcast_to(v.valid, (cap,))[idx]
                      if v.valid is not None else jnp.ones((cap,), jnp.bool_))
                new_fields.append(Field(out_name, v.type, True, v.dict))
                new_data.append(d[idx])
                new_valid.append(vv & ~empty)
                continue
            if fn == "first_value":
                idx = part_start
            else:
                # default frame: end of the current peer group (stops at the
                # live/dead boundary)
                idx = peer_end
            val = d[idx]
            vv = (jnp.broadcast_to(v.valid, (cap,))[idx]
                  if v.valid is not None else None)
            new_fields.append(Field(out_name, v.type, v.valid is not None, v.dict))
            new_data.append(val)
            new_valid.append(vv)
            continue
        if fn == "ntile":
            n_tiles = int(f_offset)
            # partition size = end - start + 1 (end stops at live/dead edge)
            psize = part_end - part_start + 1
            tile = (row_in_part * n_tiles) // jnp.maximum(psize, 1) + 1
            new_fields.append(Field(out_name, T.BIGINT, False))
            new_data.append(jnp.asarray(tile, jnp.int64))
            new_valid.append(None)
            continue

        # aggregates over the partition
        running = bool(okeys)  # default frame when ORDER BY present
        if fn == "count" and arg is None:
            vals = jnp.asarray(live_s, jnp.int64)
            m = live_s
            out_t = T.BIGINT
            dict_ = None
        else:
            v = cc.eval(arg)
            out_t = _agg_out_type(fn, v.type)
            d = jnp.broadcast_to(jnp.asarray(v.data), (cap,))
            m = live_s if v.valid is None else (live_s & jnp.broadcast_to(v.valid, (cap,)))
            dict_ = v.dict
            if fn == "count":
                vals = jnp.asarray(m, jnp.int64)
            elif fn in ("sum", "avg"):
                vals = jnp.where(m, _cast_rep(d, v.type, out_t), 0)
            else:  # min/max
                ident = _mm_ident(v.type, fn == "min")
                vals = jnp.where(m, d, jnp.asarray(ident, v.type.dtype))

        if f_frame is not None:
            # explicit ROWS/RANGE frame: prefix-sum differences for
            # sum/count/avg; scans or a doubling sparse table for min/max
            starts, ends = frame_bounds(f_frame)
            empty = starts > ends
            sm = starts - 1

            def pref_diff(P, empty=empty, ends=ends, sm=sm):
                a = P[ends]
                b = jnp.where(sm >= 0, P[jnp.clip(sm, 0, cap - 1)], 0)
                return jnp.where(empty, 0, a - b)

            cntf = pref_diff(jnp.cumsum(jnp.asarray(m, jnp.int64)))
            if fn in ("min", "max"):
                op = jnp.minimum if fn == "min" else jnp.maximum
                ident = jnp.asarray(_mm_ident(v.type, fn == "min"), vals.dtype)
                st_kind, et_kind = f_frame[1], f_frame[3]
                if st_kind == "up":
                    res = _segmented_scan(vals, part_new, op)[ends]
                elif et_kind == "uf":
                    is_end = pos == part_end
                    res = _segmented_scan(
                        vals[::-1], is_end[::-1], op)[::-1][starts]
                else:
                    res = _range_reduce(vals, op, ident, starts, ends, cap)
                new_fields.append(Field(out_name, out_t, True, dict_))
                new_data.append(jnp.where(empty, ident, res))
                new_valid.append(cntf > 0)
                continue
            if fn == "count":
                new_fields.append(Field(out_name, T.BIGINT, False))
                new_data.append(cntf)
                new_valid.append(None)
                continue
            total = pref_diff(jnp.cumsum(vals))
            if fn == "sum":
                new_fields.append(Field(out_name, out_t, True))
                new_data.append(total)
                new_valid.append(cntf > 0)
                continue
            if fn != "avg":
                raise NotImplementedError(f"window frame for {fn}")
            denom = jnp.maximum(cntf, 1)
            if out_t.is_decimal:
                res = jnp.asarray(total, jnp.float64) / (10 ** out_t.scale) / denom
            else:
                res = jnp.asarray(total, jnp.float64) / denom
            new_fields.append(Field(out_name, T.DOUBLE, True))
            new_data.append(res)
            new_valid.append(cntf > 0)
            continue

        # frame end: current peer group (running) or whole partition
        end_flags = end_peer_flags if running else end_part_flags
        if fn in ("min", "max"):
            op = jnp.minimum if fn == "min" else jnp.maximum
            run = _segmented_scan(vals, part_new, op)
            res = _peer_extend(run, end_flags, pos)
            cnt = _part_count(m, part_new, end_flags, pos)
            new_fields.append(Field(out_name, out_t, True, dict_))
            new_data.append(res)
            new_valid.append(cnt > 0)
            continue

        # sum / count / avg — segmented running scan read at the frame end
        # (whole partition when there is no ORDER BY): never a scatter
        total = _peer_extend(
            _segmented_scan(jnp.asarray(vals), part_new, jnp.add), end_flags, pos
        )
        ccnt = _part_count(m, part_new, end_flags, pos)
        if fn == "count":
            new_fields.append(Field(out_name, T.BIGINT, False))
            new_data.append(ccnt)
            new_valid.append(None)
        elif fn == "sum":
            new_fields.append(Field(out_name, out_t, True))
            new_data.append(total)
            new_valid.append(ccnt > 0)
        elif fn == "avg":
            denom = jnp.maximum(ccnt, 1)
            if out_t.is_decimal:
                res = jnp.asarray(total, jnp.float64) / (10 ** out_t.scale) / denom
            else:
                res = jnp.asarray(total, jnp.float64) / denom
            new_fields.append(Field(out_name, T.DOUBLE, True))
            new_data.append(res)
            new_valid.append(ccnt > 0)
        else:
            raise NotImplementedError(f"window function {fn}")

    out = sorted_chunk.with_columns(new_fields, new_data, new_valid)
    if limit_rank is not None:
        # segmented per-partition top-N: drop rows ranked past k right here
        # so downstream sorts/joins see ~k*partitions live rows (the filter
        # that planted limit_spec still runs above — this mask only prunes,
        # it never widens)
        keep = live_s & (limit_rank <= limit_spec[1])
        if counters is not None:
            counters["window_topn_pruned"] = (
                jnp.sum(live_s) - jnp.sum(keep))
        out = out.and_sel(keep)
    return out


def _bsearch_first(ks, lo0, hi0, thresh, cmp, iters):
    """Vectorized binary search: for each row, the first index j in
    [lo0, hi0) with cmp(ks[j], thresh) true (ks monotone over that span);
    hi0 when none. All arguments may be per-row arrays."""
    lo, hi = lo0, hi0
    n = ks.shape[0]
    for _ in range(iters):
        mid = jnp.clip((lo + hi) // 2, 0, n - 1)
        p = cmp(ks[mid], thresh)
        cont = lo < hi
        lo = jnp.where(cont & ~p, mid + 1, lo)
        hi = jnp.where(cont & p, mid, hi)
    return lo


def _range_reduce(vals, op, ident, starts, ends, cap):
    """min/max over arbitrary inclusive [starts, ends] spans: doubling sparse
    table (O(n log n) build, two gathers per row). The TPU answer to sliding
    frame min/max — no per-row loops, no scatters."""
    levels = max(1, (cap - 1).bit_length())
    tables = [vals]
    prev = vals
    for k in range(1, levels + 1):
        h = 1 << (k - 1)
        pad = jnp.full((h,), ident, prev.dtype)
        prev = op(prev, jnp.concatenate([prev[h:], pad]))
        tables.append(prev)
    stacked = jnp.stack(tables)  # (levels+1, cap)
    ln = jnp.maximum(ends - starts + 1, 1)
    k = jnp.asarray(jnp.floor(jnp.log2(jnp.asarray(ln, jnp.float64))),
                    jnp.int32)
    k = jnp.clip(k, 0, levels)
    two_k = jnp.left_shift(jnp.asarray(1, starts.dtype), k.astype(starts.dtype))
    a = stacked[k, jnp.clip(starts, 0, cap - 1)]
    b = stacked[k, jnp.clip(ends - two_k + 1, 0, cap - 1)]
    return op(a, b)


def _segmented_scan(vals, seg_start_flags, op):
    """Inclusive scan restarting at segment starts."""

    def combine(a, b):
        a_val, a_flag = a
        b_val, b_flag = b
        val = jnp.where(b_flag, b_val, op(a_val, b_val))
        return val, a_flag | b_flag

    out, _ = jax.lax.associative_scan(
        combine, (vals, seg_start_flags)
    )
    return out


def _carry_scan(vals, flags):
    """out[i] = vals at the most recent flagged position <= i (carry scan)."""

    def combine(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, av), af | bf

    out, _ = jax.lax.associative_scan(combine, (vals, flags))
    return out


def _peer_extend(run, peer_start_flags, pos):
    """RANGE frames include the whole peer group: every row takes the running
    value at the LAST row of its peer group (= position just before the next
    peer start)."""
    # row i's peer-group end = min{j >= i : next row j+1 starts a new peer}
    nxt = jnp.concatenate([peer_start_flags[1:], jnp.ones((1,), jnp.bool_)])
    end = _carry_scan(pos[::-1], nxt[::-1])[::-1]
    return run[end]


def _part_count(m, part_new, end_flags, pos):
    c = _segmented_scan(jnp.asarray(m, jnp.int64), part_new, jnp.add)
    return _peer_extend(c, end_flags, pos)


def _agg_out_type(fn, t):
    if fn in ("min", "max"):
        return t
    if fn == "count":
        return T.BIGINT
    if t.is_decimal:
        return T.DECIMAL(18, t.scale)
    if t.is_float:
        return T.DOUBLE
    return T.BIGINT


def _cast_rep(d, t, out_t):
    if t.is_decimal and out_t.is_decimal:
        x = jnp.asarray(d, jnp.int64)
        if t.scale < out_t.scale:
            x = x * (10 ** (out_t.scale - t.scale))
        return x
    return jnp.asarray(d, out_t.dtype)


def _mm_ident(t, is_min):
    if t.is_float:
        return jnp.inf if is_min else -jnp.inf
    if t.kind is T.TypeKind.BOOLEAN:
        return True if is_min else False
    info = jnp.iinfo(t.dtype)
    return info.max if is_min else info.min
