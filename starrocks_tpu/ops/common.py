"""Shared operator utilities: key normalization, lexicographic sort, compaction.

Reference behavior being re-designed: the hash-table machinery in
be/src/exec/aggregate/agg_hash_map.h and be/src/exec/join/join_hash_map.h.
TPUs have no scatter-friendly memory model, so grouping/joining is sort-based:
lexicographic multi-key sort (one fused lax.sort via jnp.lexsort), segment
boundaries, and segment reductions (SURVEY §7 "Hash tables on TPU").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import types as T
from ..column.column import Chunk
from ..exprs.compile import EVal, ExprCompiler


def eval_keys(chunk: Chunk, key_exprs) -> list:
    cc = ExprCompiler(chunk)
    out = []
    for e in key_exprs:
        v = cc.eval(e)
        d = jnp.asarray(v.data)
        shape = (chunk.capacity,) + d.shape[1:] if d.ndim > 1 \
            else (chunk.capacity,)
        data = jnp.broadcast_to(d, shape)
        # valid can come back scalar too (e.g. `x % 3`: nullness derives
        # from the literal divisor) — lexsort/boundaries need full rank
        valid = (None if v.valid is None else
                 jnp.broadcast_to(jnp.asarray(v.valid), (chunk.capacity,)))
        out.append(EVal(data, valid, v.type, v.dict, bounds=v.bounds))
    return out


def key_sort_arrays(keys, live, nulls_last_sentinel=True):
    """Build the lexsort operand list for (live-first, then key order).

    Returns list ordered least-significant-first (jnp.lexsort convention:
    the LAST array is the primary key). Dead rows sort last. NULL key values
    sort together (before non-null values of the same column).
    """
    ops = []
    for k in reversed(keys):
        if k.type.is_decimal128:
            from . import dec128 as d128

            ops.extend(d128.sort_ops(k.data, k.valid))
            continue
        ops.append(k.data)
        if k.valid is not None:
            # sort by (is_null, value): nulls form their own cluster
            ops.append(jnp.asarray(~k.valid, jnp.int8))
    ops.append(jnp.asarray(~live, jnp.int8))  # primary: live rows first
    return ops


def boundaries(keys, live, order):
    """Given sort order (indices), mark rows starting a new group.

    Row 0 of the sorted sequence is new iff live; row i is new iff live and
    any key (value or nullness) differs from row i-1.
    """
    cap = order.shape[0]
    live_s = live[order]
    diff = jnp.zeros((cap,), jnp.bool_)
    for k in keys:
        ks = k.data[order]
        neq = (jnp.any(ks[1:] != ks[:-1], axis=-1)
               if ks.ndim > 1 else ks[1:] != ks[:-1])
        d = jnp.concatenate([jnp.ones((1,), jnp.bool_), neq])
        if k.valid is not None:
            vs = k.valid[order]
            dv = jnp.concatenate([jnp.ones((1,), jnp.bool_), vs[1:] != vs[:-1]])
            # both NULL -> equal regardless of payload
            both_null = jnp.concatenate(
                [jnp.zeros((1,), jnp.bool_), (~vs[1:]) & (~vs[:-1])]
            )
            d = (d & ~both_null) | dv
        diff = diff | d
    return diff & live_s


# Phase scopes inside an operator's `sr.<kind>.<n>` scope (sql/physical.py
# `emit`): HLO metadata only, so a profiler trace says which part of a join
# or an aggregate a device operation belongs to (`sr.join.1/expand`).
PHASES = ("build", "probe", "expand", "payload", "rf", "compact",
          "limbs", "lexsort", "segments", "sort")


def phase(name: str):
    assert name in PHASES, name
    return jax.named_scope(name)


# how `live_row_index` finds a compaction's source rows: the `method` of a
# statement's `compactions` info
INDEX_METHOD = "shift"


def live_row_index(live, out_cap: int):
    """int32[out_cap]: entry j is the row of the j-th live row of `live`;
    past the live count, the last row (any row in bounds would do).

    Each live row has to move left by d = the dead rows before it. Round b
    of log2(cap) rounds moves the rows whose bit b of d is set by 2^b: a
    static shift and a select over one int32 array, at memory speed, with no
    sort, search, gather or scatter. Low bit first, two live rows i < j never
    meet: they start j - i apart and what they have moved so far differs by
    at most the dead rows between them, j - i - 1. A row's source is its
    slot plus its d. On a v5e 61 ms at 60M rows, against 88 ms for a sort of
    `where(live, i, i | 1 << 31)`, 0.46 s for one scatter of `arange` and
    22 s for a binary search on the prefix sum (tools/compact_probe.py)."""
    cap = live.shape[0]
    dead = jnp.cumsum(jnp.asarray(~live, jnp.int32))
    d = jnp.where(live, dead, -1)  # -1: no row in this slot
    for b in range((cap - 1).bit_length()):
        s = 1 << b
        sh = jnp.concatenate([d[s:], jnp.full((s,), -1, jnp.int32)])
        take = (sh >= 0) & (((sh >> b) & 1) == 1)
        stay = (d >= 0) & (((d >> b) & 1) == 0)
        d = jnp.where(take, sh, jnp.where(stay, d, -1))
    d = (d[:out_cap] if out_cap <= cap else
         jnp.concatenate([d, jnp.full((out_cap - cap,), -1, jnp.int32)]))
    return jnp.where(d >= 0, jnp.arange(out_cap, dtype=jnp.int32) + d,
                     cap - 1)


@phase("compact")
def compact(chunk: Chunk, capacity: int | None = None):
    """Gather live rows to the front (stable). Output capacity may shrink.

    The moral equivalent of the reference's Chunk::filter; only used where
    an operator genuinely needs dense rows (exchange, join build sides).
    Returns (chunk, true_live_count): when true_live_count > out capacity,
    rows were dropped — the host must recompile with a larger capacity
    (same overflow contract as hash_aggregate / hash_join_expand).
    """
    cap = chunk.capacity
    out_cap = capacity or cap
    live = chunk.sel_mask()
    n = jnp.sum(live)
    # One source-row index, then one gather a column: a gather pays per
    # OUTPUT row, ~20 ns a 32-bit element on a v5e (an int64 is two). The
    # scatter a column this replaced (`out.at[rank].set(a)`, until PR 25)
    # pays per INPUT row, kept or dropped, and 87-125 ns for an int64: it
    # was 32 of TPC-H SF10 Q3's 35 s (PERF.md section 6, PR 25).
    with jax.named_scope("index"):
        src = live_row_index(live, out_cap)
    sel = jnp.arange(out_cap) < n

    def take(a, fill):
        keep = sel.reshape((out_cap,) + (1,) * (a.ndim - 1))
        return jnp.where(keep, a[src], fill)

    with jax.named_scope("gather"):
        data = tuple(take(d, jnp.zeros((), d.dtype)) for d in chunk.data)
        valid = tuple(
            None if v is None else take(v, False) for v in chunk.valid
        )
    return Chunk(chunk.schema, data, valid, sel), n


def mix64(x):
    """splitmix64 finalizer over uint64 lanes (good avalanche, no scatter).
    THE hash of the engine: exchange routing and join fingerprints both use
    it — they must never diverge (equal keys must route AND match alike)."""
    z = jnp.asarray(x, jnp.uint64)
    z = (z ^ (z >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> 27)) * jnp.uint64(0x94D049BB133111EB)
    return z ^ (z >> 31)
