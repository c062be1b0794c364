"""Scatter-free segment reductions (the TPU aggregation substrate).

XLA lowers `jax.ops.segment_sum` & friends to scatter-add, which on TPU
serializes on duplicate indices — measured ~1000x slower than the formulations
here for the Q1-class shapes (millions of rows, few groups). This module
provides segment sum/min/max/count that never emit a scatter on the hot
paths; reference analog: the SIMD agg hash maps
(be/src/exec/aggregate/agg_hash_map.h) re-designed for the TPU.

Strategies, picked per dtype / group count / sortedness and by nothing else:
the same ladder is traced on every backend, so tests on CPU devices run what
the chip runs:

1. **Integer sums, one pass for all of a node's columns** (`seg_sums`),
   EXACT mod 2^64 — the overflow contract of a native int64 accumulator.
   Few groups: a masked reduction a column on the int64 values themselves
   (`_seg_sums_masked`), no limbs and nothing written to HBM. More groups:
   8-bit limbs of all columns side by side, summed per group by ONE bf16
   one-hot contraction on the MXU whose per-block partial sums stay below
   2^24 (exact in f32), under a loop over row blocks, then recombined with
   wrap-around arithmetic (`_seg_sums_contract`). Counts use a single limb.
2. **Broadcast-reduce** — tiny group counts, float values / min / max:
   out[g] = reduce(where(gid == g, vals, identity)); XLA fuses the compare
   into the reduction, no scatter, no materialized one-hot.
3. **Sorted prefix tricks** — group-sorted rows (the lexsort agg path,
   window partitions): sums become cumsum diffs at group boundaries found by
   searchsorted; min/max become a segmented associative scan read at the
   segment ends. All gathers, no scatters.
4. Last rung: jax.ops.segment_* (scatter) for shapes none of the above
   covers (more than `matmul_segsum_groups_max` unsorted groups).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import phase

_LIMB_BITS = 8
_LIMB_MASK = (1 << _LIMB_BITS) - 1
# rows a contraction sums in f32: block * limb_max < 2^24 keeps every
# partial sum an exactly representable integer (65,536 * 255 = 16,711,680)
_CONTRACT_ROWS = 32768
# rows one step of the contraction's loop over row blocks takes
_CONTRACT_STEP_ROWS = 1 << 20


def _matmul_groups_max() -> int:
    from ..runtime.config import config

    return config.get("matmul_segsum_groups_max")


def _bcast_groups_max() -> int:
    from ..runtime.config import config

    return config.get("bcast_segreduce_groups_max")


def _nlimbs(nbits: int) -> int:
    return max(1, (nbits + _LIMB_BITS - 1) // _LIMB_BITS)


def _seg_sums_masked(cols, gid, num_groups: int):
    """out[g] = sum(where(gid == g, v, 0)) on the int64 columns themselves,
    rows on the lane axis: no limbs, no one-hot, nothing written to HBM. XLA
    carries the 64-bit add as two u32 halves with a carry, which wraps mod
    2^64 as int64 does. All columns are operands of ONE reduce, so the
    compare, the selects and whatever computes the columns fuse into one
    pass over the rows."""
    eq = (jnp.arange(num_groups, dtype=jnp.int32)[:, None]
          == jnp.asarray(gid, jnp.int32)[None, :])
    zero = jnp.zeros((), jnp.int64)
    return list(jax.lax.reduce(
        tuple(jnp.where(eq, v[None, :], zero) for v, _ in cols),
        (zero,) * len(cols),
        lambda a, b: tuple(x + y for x, y in zip(a, b)), (1,)))


def _contract_rows(cols, gid, num_groups: int):
    """[G, L] uint64 limb totals of one run of rows: ONE one-hot and ONE
    contraction over the 8-bit limbs of all columns side by side, bf16
    operands (limbs <= 255 and 0/1 are exact), f32 accumulation over
    blocks of at most _CONTRACT_ROWS rows, each block's partial widened to
    uint64. Rows whose gid is outside [0, G) match no one-hot column."""
    n = gid.shape[0]
    block = min(n, _CONTRACT_ROWS)
    pad = -n % block  # dead rows that fill the last block

    def blocks(x, fill):
        return jnp.pad(x, (0, pad), constant_values=fill).reshape(-1, block)

    oh = (blocks(jnp.asarray(gid, jnp.int32), num_groups)[:, :, None]
          == jnp.arange(num_groups, dtype=jnp.int32)).astype(jnp.bfloat16)
    limbs = jnp.stack(
        [blocks(((jnp.asarray(v, jnp.uint64) >> (_LIMB_BITS * j))
                 & _LIMB_MASK).astype(jnp.bfloat16), 0)
         for v, nbits in cols for j in range(_nlimbs(nbits))], axis=-1)
    part = jnp.einsum("nbg,nbl->ngl", oh, limbs,
                      preferred_element_type=jnp.float32)
    return jnp.sum(part.astype(jnp.uint64), axis=0)


def _seg_sums_contract(cols, gid, num_groups: int):
    """Exact (mod 2^64) integer segment sums on the MXU, all columns in one
    contraction, under a loop over row blocks so that the limbs and the
    one-hot exist for one block at a time whatever the row count; the rows
    past the last whole block are one more (static) step."""
    n = gid.shape[0]
    step = _CONTRACT_STEP_ROWS
    nlimbs = sum(_nlimbs(nbits) for _, nbits in cols)
    tot = jnp.zeros((num_groups, nlimbs), jnp.uint64)
    whole = n // step * step
    if whole:
        def body(i, acc):
            at = i * step
            return acc + _contract_rows(
                [(jax.lax.dynamic_slice(v, (at,), (step,)), nbits)
                 for v, nbits in cols],
                jax.lax.dynamic_slice(gid, (at,), (step,)), num_groups)

        tot = jax.lax.fori_loop(0, n // step, body, tot)
    if whole < n:
        tot = tot + _contract_rows(
            [(v[whole:], nbits) for v, nbits in cols], gid[whole:], num_groups)
    out, at = [], 0
    for _, nbits in cols:
        acc = jnp.zeros((num_groups,), jnp.uint64)
        for j in range(_nlimbs(nbits)):
            acc = acc + (tot[:, at + j] << (_LIMB_BITS * j))
        at += _nlimbs(nbits)
        out.append(jnp.asarray(acc, jnp.int64))
    return out


def _seg_sum_float_bcast(vals, gid, num_groups: int):
    g = jnp.asarray(gid, jnp.int32)
    masked = jnp.where(
        g[:, None] == jnp.arange(num_groups, dtype=jnp.int32)[None, :],
        jnp.asarray(vals)[:, None],
        jnp.zeros((), vals.dtype),
    )
    return jnp.sum(masked, axis=0)


def _group_bounds_sorted(gid, num_groups: int):
    """(left, right) row index ranges per group for group-sorted gid."""
    g = jnp.asarray(gid, jnp.int32)
    slots = jnp.arange(num_groups, dtype=jnp.int32)
    left = jnp.searchsorted(g, slots, side="left")
    right = jnp.searchsorted(g, slots, side="right")
    return left, right


def _seg_sum_sorted(vals, gid, num_groups: int):
    """Cumsum-diff at group boundaries. Exact for ints (mod 2^64 wrap-around
    makes the prefix difference exact). NOT for floats: a global float prefix
    makes each group's error scale with the whole-array magnitude."""
    c = jnp.cumsum(jnp.asarray(vals))
    left, right = _group_bounds_sorted(gid, num_groups)
    n = vals.shape[0]
    p = jnp.concatenate([jnp.zeros((1,), c.dtype), c])
    out = p[jnp.clip(right, 0, n)] - p[jnp.clip(left, 0, n)]
    return out


def _seg_sum_sorted_float(vals, gid, num_groups: int):
    """Float segment sums for group-sorted rows: a segmented scan that
    RESTARTS at each group boundary (no cross-group cancellation), read at
    the group ends."""
    v = jnp.asarray(vals)
    g = jnp.asarray(gid, jnp.int32)
    starts = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), g[1:] != g[:-1]])

    def combine(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, av + bv), af | bf

    run, _ = jax.lax.associative_scan(combine, (v, starts))
    left, right = _group_bounds_sorted(g, num_groups)
    n = v.shape[0]
    out = run[jnp.clip(right - 1, 0, n - 1)]
    return jnp.where(right > left, out, jnp.zeros((), v.dtype))


def _segmented_scan_minmax(vals, gid, is_min: bool):
    """Running min/max within each group (group-sorted rows)."""
    g = jnp.asarray(gid, jnp.int32)

    def combine(a, b):
        ga, va = a
        gb, vb = b
        same = ga == gb
        red = jnp.minimum(va, vb) if is_min else jnp.maximum(va, vb)
        return gb, jnp.where(same, red, vb)

    _, scanned = jax.lax.associative_scan(combine, (g, jnp.asarray(vals)))
    return scanned


def _seg_minmax_sorted(vals, gid, num_groups: int, is_min: bool, identity):
    scanned = _segmented_scan_minmax(vals, gid, is_min)
    left, right = _group_bounds_sorted(gid, num_groups)
    n = vals.shape[0]
    at_end = scanned[jnp.clip(right - 1, 0, n - 1)]
    return jnp.where(right > left, at_end, jnp.asarray(identity, vals.dtype))


def _seg_minmax_bcast(vals, gid, num_groups: int, is_min: bool, identity):
    g = jnp.asarray(gid, jnp.int32)
    masked = jnp.where(
        g[:, None] == jnp.arange(num_groups, dtype=jnp.int32)[None, :],
        jnp.asarray(vals)[:, None],
        jnp.asarray(identity, vals.dtype),
    )
    return (jnp.min if is_min else jnp.max)(masked, axis=0)


def _global_sum(vals, gid):
    """num_groups == 1: one fused masked reduction, no scatter / one-hot
    (the gid==0 compare folds away when gid is the constant zeros of the
    no-group-key path)."""
    m = jnp.asarray(gid, jnp.int32) == 0
    return jnp.sum(jnp.where(m, vals, jnp.zeros((), vals.dtype)),
                   keepdims=True)


def _seg_sums_int(cols, gid, num_groups: int, sorted_gid: bool):
    """(sums, formulation) of int64 columns `[(vals, nbits)]`, in one pass
    over the rows where the scatter-free strategies apply. Which one
    follows the group count alone, a static shape: up to the
    broadcast-reduce limit that floats, min and max have (64 groups) a
    masked reduction (work ~ G a row and column, no MXU), above it one
    contraction (the one-hot is real MXU work then). On a v5e at 60M rows
    and six columns the two meet at 64 groups, 84 against 78 ms; at 6 it is
    28 against 76, at 1,024 943 against 220 (tools/segsum_probe.py; PERF.md
    section 6, PR 27)."""
    if num_groups == 1:
        return [_global_sum(v, gid) for v, _ in cols], "global"
    if num_groups <= _bcast_groups_max():
        with phase("limbs"):
            return _seg_sums_masked(cols, gid, num_groups), "masked"
    if num_groups <= _matmul_groups_max():
        with phase("limbs"):
            return _seg_sums_contract(cols, gid, num_groups), "contract"
    if sorted_gid:
        return [_seg_sum_sorted(v, gid, num_groups)
                for v, _ in cols], "sorted"
    return [jax.ops.segment_sum(v, gid, num_segments=num_groups,
                                indices_are_sorted=sorted_gid)
            for v, _ in cols], "scatter"


def _seg_sum_float(vals, gid, num_groups: int, sorted_gid: bool):
    if num_groups == 1:
        return _global_sum(vals, gid)
    if num_groups <= _bcast_groups_max():
        return _seg_sum_float_bcast(vals, gid, num_groups)
    if sorted_gid:
        return _seg_sum_sorted_float(vals, gid, num_groups)
    return jax.ops.segment_sum(vals, gid, num_segments=num_groups,
                               indices_are_sorted=sorted_gid)


@phase("segments")
def seg_sums(cols, gid, num_groups: int, *, sorted_gid: bool = False,
             info: dict | None = None):
    """Segment sums of several columns over one `gid`, without scatters
    where possible: `cols` = [(vals, nbits), ...], one result a column, in
    order. All the integer (and bool) columns of the batch are summed
    together (`_seg_sums_int`), floats one by one; an array handed in twice
    is summed once.

    gid must map dead rows OUT of [0, num_groups). `nbits` bounds the value
    bit-width for integer inputs (e.g. 1 for 0/1 liveness counts): fewer
    limbs where the formulation has limbs. Results match
    jax.ops.segment_sum exactly for ints (mod 2^64); float results differ
    only by reduction order. `info`, when given and the batch has integer
    columns, is filled at trace time: rows, groups, integer columns handed
    in, distinct ones summed, limb columns made, and the formulation."""
    place: dict = {}  # id of an array handed in -> its index in `uniq`
    uniq = []
    for vals, nbits in cols:
        if id(vals) not in place:
            place[id(vals)] = len(uniq)
            v = jnp.asarray(vals)
            if v.dtype == jnp.bool_ or jnp.issubdtype(v.dtype, jnp.integer):
                v = jnp.asarray(v, jnp.int64)
            uniq.append((v, nbits))
    ints = [i for i, (v, _) in enumerate(uniq) if v.dtype == jnp.int64]
    results = [None if v.dtype == jnp.int64
               else _seg_sum_float(v, gid, num_groups, sorted_gid)
               for v, _ in uniq]
    if ints:
        sums, formulation = _seg_sums_int(
            [uniq[i] for i in ints], gid, num_groups, sorted_gid)
        for i, r in zip(ints, sums):
            results[i] = r
        if info is not None:
            info.update(
                rows=int(gid.shape[0]), groups=num_groups,
                columns=sum(place[id(vals)] in ints for vals, _ in cols),
                distinct=len(ints),
                limbs=(sum(_nlimbs(uniq[i][1]) for i in ints)
                       if formulation == "contract" else 0),
                formulation=formulation)
    return [results[place[id(vals)]] for vals, _ in cols]


def seg_sum(vals, gid, num_groups: int, *, sorted_gid: bool = False,
            nbits: int = 64):
    """`seg_sums` of one column."""
    return seg_sums([(vals, nbits)], gid, num_groups,
                    sorted_gid=sorted_gid)[0]


def seg_count(live, gid, num_groups: int, *, sorted_gid: bool = False):
    """Per-group count of live rows (a one-limb column)."""
    return seg_sum(live, gid, num_groups, sorted_gid=sorted_gid, nbits=1)


@phase("segments")
def _seg_minmax(vals, gid, num_groups: int, is_min: bool, identity,
                sorted_gid: bool):
    vals = jnp.asarray(vals)
    if num_groups == 1:
        m = jnp.asarray(gid, jnp.int32) == 0
        masked = jnp.where(m, vals, jnp.asarray(identity, vals.dtype))
        return (jnp.min if is_min else jnp.max)(masked, keepdims=True)
    if num_groups <= _bcast_groups_max():
        return _seg_minmax_bcast(vals, gid, num_groups, is_min, identity)
    if sorted_gid:
        return _seg_minmax_sorted(vals, gid, num_groups, is_min, identity)
    seg = jax.ops.segment_min if is_min else jax.ops.segment_max
    return seg(vals, gid, num_segments=num_groups, indices_are_sorted=sorted_gid)


def seg_min(vals, gid, num_groups: int, *, identity, sorted_gid: bool = False):
    """Segment min; empty groups get `identity` (callers mask them out)."""
    return _seg_minmax(vals, gid, num_groups, True, identity, sorted_gid)


def seg_max(vals, gid, num_groups: int, *, identity, sorted_gid: bool = False):
    return _seg_minmax(vals, gid, num_groups, False, identity, sorted_gid)


@phase("segments")
def seg_first_index(gid, num_groups: int, n: int):
    """First row index of each group for group-sorted gid (empty -> n)."""
    left, right = _group_bounds_sorted(gid, num_groups)
    return jnp.where(right > left, left, n)
