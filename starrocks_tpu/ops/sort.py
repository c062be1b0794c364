"""ORDER BY / TopN / LIMIT operators.

Reference behavior: be/src/exec/chunks_sorter.h:44 (full sort),
chunks_sorter_topn.h:26 (heap TopN), and the merge-path parallel merge
kernels (be/src/compute_env/sorting/merge_path.h). On TPU, XLA's lax.sort is
already a parallel bitonic-class sort; this module narrows what feeds it:

- packed-key sort: bounded keys (dict codes, bools, stats-bounded ints —
  the same domain machinery as the aggregate's packed-gid path) encode into
  ONE order-preserving int64 (descending via complement, NULLS FIRST/LAST
  via a sentinel bit per nullable key, dead rows -> INT64_MAX), so the
  multi-operand lexsort comparator collapses to a single int64 compare;
- threshold TopN: ORDER BY .. LIMIT k over a packed key runs a partial
  select (lax.top_k) — rows past the k-th key never reach a gather, and
  the output capacity SHRINKS to ~k (the reference's heap-TopN runtime
  filter re-designed branch-free);
- the distributed merge phase lives in parallel/ (gather + re-sort, or
  all_gather of per-shard TopN).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..column.column import Chunk, pad_capacity
from .common import eval_keys, phase

_I64MAX = jnp.iinfo(jnp.int64).max

# threshold top-N only pays while k stays far below the input size; past
# this the full packed argsort is at least as good (and top_k's k*log(n)
# candidate handling stops winning)
TOPN_MAX_K = 4096


def sort_operands(keys, sort_keys) -> list:
    """lexsort operand list (least-significant first, WITHOUT the liveness
    operand) for evaluated sort keys. Shared by the device sort and the
    host-merge spill path so both order rows with the SAME comparator."""
    ops = []
    for k, (_, asc, nulls_first) in zip(reversed(keys),
                                        reversed(list(sort_keys))):
        if k.type.is_decimal128:
            from .dec128 import cmp_limbs

            _M32 = 0xFFFFFFFF
            for limb in reversed(cmp_limbs(k.data)):  # ls-first operands
                ops.append(limb if asc else (_M32 - limb))
            if k.valid is not None:
                ops.append(jnp.asarray(
                    k.valid if nulls_first else ~k.valid, jnp.int8))
            continue
        d = k.data
        if d.dtype == jnp.bool_:
            d = jnp.asarray(d, jnp.int8)
        dd = d if asc else _descending(d)
        ops.append(dd)
        if k.valid is not None:
            # the flag is more significant than the value (appended later);
            # ascending sort puts 0 first, so: nulls_first -> valid flag (null=0)
            ops.append(jnp.asarray(k.valid if nulls_first else ~k.valid, jnp.int8))
    return ops


def packed_order_key(keys, sort_keys, live):
    """ONE order-preserving int64 per row encoding (live-first, key order),
    or None when a key is unbounded / the widths overflow 62 bits.

    Per key (most-significant first): value bits = (v - lo) for ASC,
    (hi - v) for DESC; nullable keys prepend one sentinel bit placing the
    NULL block first or last. Dead rows take INT64_MAX (always past every
    live encoding: total live bits <= 62). Reuses the aggregate's
    _key_domain so "packable" can never diverge between grouping and
    ordering (sql/physical.py:choose_key_packing is the join-side analog
    of the same bit-width discipline)."""
    from ..runtime.config import config as _cfg

    if not keys or not _cfg.get("enable_packed_sort_keys"):
        return None
    from .aggregate import _key_domain

    parts = []
    total_bits = 0
    for k, (_, asc, nulls_first) in zip(keys, sort_keys):
        dom = _key_domain(k)
        if dom is None:
            return None
        base, lo = dom
        base = max(int(base), 1)
        w = max((base - 1).bit_length(), 1)
        code = jnp.clip(jnp.asarray(k.data, jnp.int64) - lo, 0, base - 1)
        if not asc:
            code = (base - 1) - code
        if k.valid is not None:
            # sentinel bit above the value bits: NULLs form one block at
            # the requested end, value bits of NULL rows zero out
            null_bit = 0 if nulls_first else 1
            bit = jnp.where(k.valid, 1 - null_bit, null_bit)
            code = jnp.where(k.valid, code, 0) | (
                jnp.asarray(bit, jnp.int64) << w)
            w += 1
        parts.append((code, w))
        total_bits += w
        if total_bits > 62:
            return None
    packed = jnp.zeros((live.shape[0],), jnp.int64)
    for code, w in parts:
        packed = (packed << w) | code
    return jnp.where(live, packed, _I64MAX)


def sort_chunk(chunk: Chunk, sort_keys, limit: int | None = None,
               counters: dict | None = None) -> Chunk:
    """sort_keys: tuple of (expr, asc: bool, nulls_first: bool).

    Dead rows always sort last; output sel marks the first n (or limit) rows.
    With a packable key and a small LIMIT the output capacity SHRINKS to
    ~pad_capacity(limit) — the threshold top-N path never materializes
    pruned rows. `counters` (when given) receives device scalars the
    executor turns into profile counters ('topn_rows_pruned')."""
    cap = chunk.capacity
    live = chunk.sel_mask()
    keys = eval_keys(chunk, tuple(e for e, _, _ in sort_keys))
    n = jnp.sum(live)

    from ..runtime.config import config as _cfg

    strategy = _cfg.get("topn_strategy")
    packed = None if strategy == "lexsort" else packed_order_key(
        keys, sort_keys, live)
    if packed is not None:
        if (limit is not None and 0 < limit <= TOPN_MAX_K
                and pad_capacity(limit) < cap):
            kk = pad_capacity(limit)
            # the kk smallest packed keys, ascending, stable on ties
            # (top_k breaks ties by lower index, as a stable ascending
            # argsort does); `~packed` reverses int64 order exactly, where
            # negation would overflow on INT64_MIN
            with phase("sort"):
                _, order = jax.lax.top_k(~packed, kk)
            out = chunk.take(order)
            k = jnp.minimum(n, limit)
            if counters is not None:
                counters["topn_rows_pruned"] = jnp.maximum(n - limit, 0)
            return out.with_sel(jnp.arange(kk) < k)
        with phase("sort"):
            order = jnp.argsort(packed, stable=True)
    else:
        ops = sort_operands(keys, sort_keys)
        ops.append(jnp.asarray(~live, jnp.int8))  # live rows first
        with phase("sort"):
            order = jnp.lexsort(tuple(ops))

    out = chunk.take(order)
    k = n if limit is None else jnp.minimum(n, limit)
    sel = jnp.arange(cap) < k
    return out.with_sel(sel)


def _descending(d):
    if jnp.issubdtype(d.dtype, jnp.floating):
        return -d
    if d.dtype == jnp.uint32 or d.dtype == jnp.uint64:
        return jnp.iinfo(d.dtype).max - d
    return -d  # signed ints: negation safe except INT_MIN (accepted caveat)


def limit_chunk(chunk: Chunk, limit: int, offset: int = 0) -> Chunk:
    """Keep `limit` live rows after skipping `offset` (row order = physical)."""
    live = chunk.sel_mask()
    rank = jnp.cumsum(live) - 1  # rank among live rows
    keep = live & (rank >= offset) & (rank < offset + limit)
    return chunk.with_sel(keep)
