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
    # scatter-based (stable): live row i lands at slot rank(i). Indices are
    # unique, so the scatter is fast on TPU too (serialization only bites on
    # duplicates) — vs the previous argsort formulation, O(n log n) and the
    # dominant cost of every exchange at large capacities.
    pos = jnp.cumsum(jnp.asarray(live, jnp.int32)) - 1
    idx = jnp.where(live, pos, out_cap)  # dead/overflow rows drop
    idx = jnp.where(idx >= out_cap, out_cap, idx)

    def scat(a, fill):
        out = jnp.full((out_cap,), fill, a.dtype)
        return out.at[idx].set(a, mode="drop")

    data = tuple(scat(d, jnp.zeros((), d.dtype)) for d in chunk.data)
    valid = tuple(
        None if v is None else scat(v, False) for v in chunk.valid
    )
    sel = jnp.arange(out_cap) < n
    return Chunk(chunk.schema, data, valid, sel), n


def mix64(x):
    """splitmix64 finalizer over uint64 lanes (good avalanche, no scatter).
    THE hash of the engine: exchange routing and join fingerprints both use
    it — they must never diverge (equal keys must route AND match alike)."""
    z = jnp.asarray(x, jnp.uint64)
    z = (z ^ (z >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> 27)) * jnp.uint64(0x94D049BB133111EB)
    return z ^ (z >> 31)
