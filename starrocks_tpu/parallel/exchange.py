"""Exchange: data movement between mesh shards (runs INSIDE shard_map).

Reference behavior: ExchangeSinkOperator -> SinkBuffer -> bRPC transmit_chunk
-> DataStreamMgr -> ExchangeSourceOperator
(be/src/exec/pipeline/exchange/exchange_sink_operator.h:47,
 compute_env/data_stream/data_stream_mgr.h:101), with partition strategies
UNPARTITIONED (broadcast/gather), HASH_PARTITIONED, RANDOM
(gensrc/thrift/Partitions.thrift:41). On TPU these become compiled
collectives over ICI:

- broadcast / gather       -> lax.all_gather
- hash partition (shuffle) -> bucket + pad + lax.all_to_all
- backpressure/flow control -> not needed: the exchange is a compiled
  collective; skew shows up as padding, handled by a skew factor + a
  true-count overflow check the host can react to (the adaptive-dop analog).

All functions here take/return Chunks whose arrays are *local shards* (they
are called inside shard_map, where a Chunk pytree holds per-device views).

Names on the work, for whoever reads a device trace: every exchange sits
under a `jax.named_scope("exchange")` inside its caller's `sr.<kind>.<n>`
operator scope, split into `exchange/pack` (the bucket of each row, the
argsort and the scatters into the padded send buffer) and
`exchange/collective` (the all_to_all or all_gather itself). Plain scopes,
not members of ops/common.PHASES. What one exchange puts on the interconnect
is known from its shapes alone; each function appends that to the `log` it
is given while it traces (`_shape`), and the host counts it once per run of
the program (runtime/dist_executor.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..column.column import Chunk
from ..ops.common import eval_keys


def _tree_chunk(chunk: Chunk, fn):
    data = tuple(fn(d) for d in chunk.data)
    valid = tuple(None if v is None else fn(v) for v in chunk.valid)
    sel = None if chunk.sel is None else fn(chunk.sel)
    return data, valid, sel


def _row_bytes(data, valid=(), sel=None) -> int:
    """Bytes one row (one slot of the leading axis) takes over the data
    columns, the validity columns that exist and the live mask if any."""
    return sum(a.dtype.itemsize * math.prod(a.shape[1:])
               for a in (*data, *valid, sel) if a is not None)


def _shape(op: str, slots: int, row_bytes: int, n_shards: int,
           check: str | None = None) -> dict:
    """One exchange as its static shapes give it. `slots`: rows of send
    buffer on one shard, padding included (an all_to_all's n*C, an
    all_gather's local capacity). `bytes`: what one shard puts on the
    interconnect — an all_to_all keeps one bucket of its n at home, an
    all_gather sends its rows to the n-1 others. `check`: the capacity key
    under which the fullest bucket comes back to the host (all_to_all)."""
    sent = (slots // n_shards if op == "all_to_all" else slots) * (n_shards - 1)
    return {"op": op, "slots": slots, "bytes": sent * row_bytes,
            "check": check}


def all_gather_chunk(chunk: Chunk, axis: str, log: list | None = None) -> Chunk:
    """Every shard receives all rows (UNPARTITIONED/broadcast exchange).

    Local capacity C -> output capacity n*C on every shard."""
    def ag(x):
        return lax.all_gather(x, axis, axis=0, tiled=True)

    with jax.named_scope("exchange"), jax.named_scope("collective"):
        data, valid, sel = _tree_chunk(chunk, ag)
    if log is not None:
        log.append(_shape(
            "all_gather", chunk.capacity,
            _row_bytes(chunk.data, chunk.valid, chunk.sel),
            lax.axis_size(axis)))
    if sel is None:
        sel = jnp.ones((data[0].shape[0],), jnp.bool_)
    return Chunk(chunk.schema, data, valid, sel)


def hash_hash64(x: jnp.ndarray) -> jnp.ndarray:
    """Cheap 64-bit integer mix (shared splitmix64; see ops.common.mix64)."""
    from ..ops.common import mix64

    return mix64(x)


def shuffle_chunk(
    chunk: Chunk,
    key_exprs,
    axis: str,
    n_shards: int,
    bucket_capacity: int,
    bit_widths=None,
    log: list | None = None,
    check: str | None = None,
):
    """HASH_PARTITIONED exchange: rows travel to shard hash(key) % n.

    Returns (chunk_out, max_bucket_count):
    - chunk_out: local capacity n_shards*bucket_capacity, rows this shard
      received; dead slots masked.
    - max_bucket_count: traced scalar = largest per-bucket row count BEFORE
      padding; host checks <= bucket_capacity (else recompile bigger).
    NULL keys hash like a value (bucket 0) so group-by-NULL still works;
    `pack_keys`'s ok flag is ignored here on purpose (exchange must move
    every live row).
    """
    with jax.named_scope("exchange"):
        with jax.named_scope("pack"):
            live = chunk.sel_mask()
            # dead rows -> bucket n (dropped); NULL-key live rows still travel
            keys = eval_keys(chunk, key_exprs)
            mix = jnp.zeros((chunk.capacity,), jnp.uint64)
            for k in keys:
                kd = jnp.asarray(k.data, jnp.int64)
                if k.valid is not None:
                    kd = jnp.where(k.valid, kd, jnp.int64(-1))
                kd_u = (jnp.asarray(kd, jnp.uint64)
                        * jnp.uint64(0x9E3779B97F4A7C15))
                mix = hash_hash64(mix ^ kd_u)
            bucket = jnp.asarray(mix % jnp.uint64(n_shards), jnp.int32)
            bucket = jnp.where(live, bucket, n_shards)
        return _exchange_by_bucket(chunk, bucket, axis, n_shards,
                                   bucket_capacity, log, check)


def _exchange_by_bucket(chunk, bucket, axis, n_shards, bucket_capacity,
                        log=None, check=None):
    """Route each live row to shard `bucket[row]` (dead rows carry bucket
    n_shards). Shared tail of the HASH and RANGE partition exchanges, inside
    their `exchange` scope: stable-pack rows per destination bucket, pad to
    bucket_capacity (`pack`), one lax.all_to_all a column (`collective`).
    Returns (chunk_out, max_bucket_count)."""
    out_cap = n_shards * bucket_capacity
    with jax.named_scope("pack"):
        order = jnp.argsort(bucket, stable=True)
        b_sorted = bucket[order]
        counts = jnp.bincount(bucket, length=n_shards + 1)[:n_shards]
        starts = jnp.cumsum(counts) - counts
        pos_in_bucket = jnp.arange(chunk.capacity) - starts[jnp.clip(b_sorted, 0, n_shards - 1)]
        ok = (b_sorted < n_shards) & (pos_in_bucket < bucket_capacity)

        # not-ok rows (dead / bucket overflow) are routed out of bounds so
        # the "drop" scatter mode discards them instead of colliding with
        # real slots
        dest = jnp.where(
            ok, b_sorted * bucket_capacity + pos_in_bucket, out_cap
        )

        def scatter(x):
            # wide columns ([cap, W] ARRAY/DECIMAL128/sketch planes) route
            # row-wise: dest indexes the leading axis
            buf = jnp.zeros((out_cap,) + x.shape[1:], x.dtype)
            return buf.at[dest].set(x[order], mode="drop")

        data = tuple(scatter(d) for d in chunk.data)
        valid = tuple(None if v is None else scatter(v) for v in chunk.valid)
        live_buf = jnp.zeros((out_cap,), jnp.bool_).at[dest].set(
            ok, mode="drop")
        full = jnp.max(counts)

    def a2a(x):
        # [n*C, ...] -> [n, C, ...] -> swap shard/bucket -> my bucket from all
        return lax.all_to_all(
            x.reshape((n_shards, bucket_capacity) + x.shape[1:]), axis,
            split_axis=0, concat_axis=0, tiled=False,
        ).reshape((out_cap,) + x.shape[1:])

    if log is not None:
        log.append(_shape("all_to_all", out_cap,
                          _row_bytes(data, valid, live_buf), n_shards, check))
    with jax.named_scope("collective"):
        data = tuple(a2a(d) for d in data)
        valid = tuple(None if v is None else a2a(v) for v in valid)
        sel = a2a(live_buf)
    return Chunk(chunk.schema, data, valid, sel), full


def range_partition_chunk(
    chunk: Chunk,
    rank: jnp.ndarray,
    axis: str,
    n_shards: int,
    bucket_capacity: int,
    sample_per_shard: int = 64,
    log: list | None = None,
    check: str | None = None,
):
    """RANGE exchange: rows travel to shards by sampled splitters of `rank`
    (a totally-ordered per-row sort key; dead rows may hold anything). After
    the exchange, shard i's live rows all rank <= shard i+1's — a local sort
    per shard then yields GLOBAL order across the device axis, so the final
    tiled all_gather concatenates to a globally sorted table. This is the
    TPU analog of the reference's merge-path distributed sort
    (be/src/compute_env/sorting/merge_path.h): splitters replace the
    merge-path diagonal search; the all_to_all replaces streamed merges.

    Returns (chunk_out, max_bucket_count) — same overflow contract as
    shuffle_chunk (host checks max_bucket_count <= bucket_capacity).
    """
    with jax.named_scope("exchange"):
        with jax.named_scope("pack"):
            live = chunk.sel_mask()
            if jnp.issubdtype(rank.dtype, jnp.floating):
                big = jnp.asarray(jnp.inf, rank.dtype)
            else:
                big = jnp.asarray(jnp.iinfo(rank.dtype).max, rank.dtype)
            r = jnp.where(live, rank, big)

            # evenly spaced live quantiles of the locally sorted ranks; every
            # shard gathers every shard's sample, so all shards derive
            # IDENTICAL splitters
            srt = jnp.sort(r)
            n_live = jnp.sum(live)
            idx = (jnp.arange(sample_per_shard)
                   * jnp.maximum(n_live, 1)) // sample_per_shard
            sample = srt[jnp.clip(idx, 0, chunk.capacity - 1)]
        # empty shards contribute `big` samples (srt is all-big), skewing
        # splitters upward — a balance issue only, never a correctness one
        with jax.named_scope("collective"):
            all_samples = lax.all_gather(sample, axis, axis=0, tiled=True)
        if log is not None:
            log.append(_shape("all_gather", sample_per_shard,
                              _row_bytes([sample]), n_shards))
        with jax.named_scope("pack"):
            ss = jnp.sort(all_samples)
            total = n_shards * sample_per_shard
            splitters = ss[(jnp.arange(1, n_shards) * total) // n_shards]

            bucket = jnp.asarray(
                jnp.searchsorted(splitters, r, side="left"), jnp.int32)
            bucket = jnp.where(live, bucket, n_shards)
        return _exchange_by_bucket(chunk, bucket, axis, n_shards,
                                   bucket_capacity, log, check)
