"""Pallas TPU kernels for hot aggregation paths.

The headline benchmark group-bys (TPC-H Q1: 4 groups; SSB: dozens) have
dictionary-bounded key domains, so aggregation can skip the lexsort entirely:
per-row group ids become a one-hot matrix and the per-group sums are ONE
matmul — putting the aggregation FLOPs on the MXU instead of sort networks
(reference analog: the SIMD-optimized fixed-size agg hash maps,
be/src/exec/aggregate/agg_hash_map.h, re-designed for a systolic array).

`segment_sum_onehot` is the portable XLA formulation (einsum — XLA lowers it
to MXU matmuls on TPU). `segment_sum_pallas` is the explicit Pallas kernel:
a grid over row blocks, each block building its one-hot tile in VMEM and
accumulating partial sums into a [G, M] accumulator — HBM->VMEM streaming
handled by the Pallas pipeline.

STATUS: wired behind `SET segment_strategy = 'pallas'` (ops/segment.py
_seg_sum_pallas): float segment sums route through this kernel — interpret
mode off-TPU (correctness-testable without hardware,
tests/test_lowcard_agg.py). Integer/decimal sums keep the exact strategies
(f32 accumulation here).

ON A TPU (v5e, jax 0.9.0, 2026-09-26; tools/pallas_probe.py is the
reproducer, ROADMAP A6 the follow-up): only segment_sum_pallas compiles
through Mosaic (and matches segment_sum_onehot) — its operands are
int32/float32 and it is traced with x64 off. The engine enables
jax_enable_x64 globally and Mosaic refuses 64-bit types, so the four kernels
over int64 refs (top-N select, hash build, hash probe, sorted probe) fail
while lowering their 1-D int64 blocks and int64 constants; they need a
2x32-bit key layout, not a patch. A `*_strategy` that selects one of them
therefore fails loudly on the chip — there is no interpret or reference
fallback there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def segment_sum_onehot(gid, values, num_groups: int):
    """[N] int32 group ids + [N, M] float32 values -> [G, M] sums (XLA path).

    Dead rows must carry gid == num_groups (one extra one-hot column that is
    discarded)."""
    onehot = jax.nn.one_hot(gid, num_groups + 1, dtype=values.dtype, axis=-1)
    out = jnp.einsum("ng,nm->gm", onehot, values)
    return out[:num_groups]


def _agg_block_kernel(gid_ref, val_ref, acc_ref, *, num_groups: int):
    import jax.experimental.pallas as pl
    import jax.numpy as jnp

    i = pl.program_id(0)
    gid = gid_ref[...]  # [B]
    vals = val_ref[...]  # [B, M]
    # one-hot tile [B, G+1]; the +1 column absorbs dead rows
    oh = (gid[:, None] == jnp.arange(num_groups + 1)[None, :]).astype(vals.dtype)
    partial = jnp.dot(oh.T, vals, preferred_element_type=jnp.float32)  # [G+1, M]

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += partial


def segment_sum_pallas(gid, values, num_groups: int, block: int = 2048,
                       interpret: bool = False):
    """Pallas grid kernel: stream row blocks, accumulate [G+1, M] in VMEM."""
    import jax.experimental.pallas as pl

    n, m = values.shape
    assert n % block == 0, f"rows {n} must be a multiple of block {block}"
    grid = (n // block,)
    kernel = functools.partial(_agg_block_kernel, num_groups=num_groups)
    # traced with x64 off: the engine enables it globally, and then the
    # kernel's iota and the index maps' literal zeros are int64, which
    # Mosaic refuses ("64-bit types are not supported"); the operands here
    # are int32/float32 either way
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block,), lambda i: (i,)),
                pl.BlockSpec((block, m), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((num_groups + 1, m), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((num_groups + 1, m), jnp.float32),
            interpret=interpret,
        )(gid, values)
    return out[:num_groups]


# --- TopN partial select: per-block selection for threshold TopN -------------


def _topn_block_kernel(neg_ref, vals_ref, idx_ref, *, k: int, block: int):
    """Top-k selection over one row block: k rounds of (max, first-argmax,
    mask out) — branch-free, ties resolve to the LOWEST index so the
    candidate stream reproduces a stable ascending sort of the original
    keys. The bitonic-network alternative sorts the whole block (log^2 B
    stages); for k << B the selection ladder does k reductions instead,
    which is the partial-select shape the reference's heap TopN
    (chunks_sorter_topn.h) amortizes on CPU."""
    import jax.experimental.pallas as pl
    import jax.numpy as jnp

    base = pl.program_id(0) * block
    x = neg_ref[...]                      # [B] int64, bigger = better
    lanes = jnp.arange(block, dtype=jnp.int32)
    floor = jnp.iinfo(jnp.int64).min
    vals, idxs = [], []
    for _ in range(k):                    # static unroll
        mv = jnp.max(x)
        pos = jnp.argmax(x)               # first occurrence on ties
        vals.append(mv)
        idxs.append(base + pos)
        x = jnp.where(lanes == pos, floor, x)
    vals_ref[...] = jnp.stack(vals)
    idx_ref[...] = jnp.stack(idxs).astype(jnp.int32)


def topn_select_pallas(neg, k: int, block: int = 1024,
                       interpret: bool = False):
    """Per-block top-k candidates of `neg` ([N] int64, LARGEST-first):
    returns (vals [nblocks*k], idx [nblocks*k]) — the caller reduces the
    candidate set with one final top_k (k·nblocks rows instead of N ever
    reaching it). Flag-gated behind `SET topn_strategy='pallas'`; interpret
    mode off-TPU so correctness is testable without hardware."""
    import functools

    import jax.experimental.pallas as pl

    n = neg.shape[0]
    assert n % block == 0, f"rows {n} must be a multiple of block {block}"
    assert k <= block, f"k {k} must fit one block {block}"
    grid = (n // block,)
    kernel = functools.partial(_topn_block_kernel, k=k, block=block)
    vals, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=[
            pl.BlockSpec((k,), lambda i: (i,)),
            pl.BlockSpec((k,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n // block * k,), jnp.int64),
            jax.ShapeDtypeStruct((n // block * k,), jnp.int32),
        ],
        interpret=interpret,
    )(neg)
    return vals, idx


# --- join hash table: open-addressing build + vectorized probe ---------------

_EMPTY = (1 << 63) - 1  # int64 max: the engine-wide NULL/dead key sentinel


def _mix64(x):
    """splitmix64 finalizer (ops/common.mix64 inlined so the kernel body
    stays dependency-free for Mosaic lowering)."""
    z = jnp.asarray(x, jnp.uint64)
    z = (z ^ (z >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> 27)) * jnp.uint64(0x94D049BB133111EB)
    return z ^ (z >> 31)


def _hash_build_kernel(keys_ref, tkey_ref, trow_ref, *, table_size: int):
    """Open-addressing (linear probing) hash-table BUILD over unique keys,
    branch-free: each round every unplaced key claims its current probe
    slot with a scatter-min of its row id; winners write (key, row) and
    park, losers advance their displacement. Keys equal to the engine's
    NULL/dead sentinel never insert. Termination: the table has spare
    capacity (load factor <= 0.5), every key's probe sequence walks the
    whole pow-2 table, and displacements only grow — the while_loop drains
    in O(max displacement) rounds (reference analog: the linear-probing
    insert of be/src/exec/join_hash_map.h, re-designed as data-parallel
    claim rounds for the VPU)."""
    import jax.numpy as jnp

    keys = keys_ref[...]                       # [N] int64
    n = keys.shape[0]
    mask = table_size - 1
    h = jnp.asarray(_mix64(keys.view(jnp.uint64)), jnp.int64) & mask
    rowid = jnp.arange(n, dtype=jnp.int32)

    def round_(state):
        tkey, trow, disp, placed = state
        slot = (h + disp) & mask
        occupied = tkey[slot] != _EMPTY
        want = (~placed) & (~occupied)
        cand = jnp.where(want, slot, table_size)   # parked rows scatter-drop
        claim = jnp.full((table_size + 1,), n, jnp.int32).at[cand].min(
            rowid, mode="drop")
        won = want & (claim[jnp.minimum(slot, table_size)] == rowid)
        wslot = jnp.where(won, slot, table_size)
        tkey = tkey.at[wslot].set(keys, mode="drop")
        trow = trow.at[wslot].set(rowid, mode="drop")
        placed = placed | won
        disp = disp + jnp.where(placed, 0, 1)
        return tkey, trow, disp, placed

    init = (
        jnp.full((table_size,), _EMPTY, jnp.int64),
        jnp.full((table_size,), -1, jnp.int32),
        jnp.zeros((n,), jnp.int32),
        keys == _EMPTY,  # sentinel (NULL/dead) rows never insert
    )
    tkey, trow, _, _ = jax.lax.while_loop(
        lambda s: jnp.any(~s[3]), round_, init)
    tkey_ref[...] = tkey
    trow_ref[...] = trow


def hash_build_pallas(keys, table_size: int, interpret: bool = False):
    """Build the open-addressing table for `keys` ([N] int64, unique except
    the NULL/dead sentinel): returns (table_key [T] int64, table_row [T]
    int32, row -1 = empty). table_size must be a power of 2 >= 2*N (load
    factor <= 0.5 keeps expected probe chains ~1.5). Flag-gated behind
    `SET join_probe_strategy = 'pallas'`; interpret mode off-TPU."""
    import jax.experimental.pallas as pl

    assert table_size & (table_size - 1) == 0, "table size must be pow-2"
    assert table_size >= 2 * keys.shape[0], "load factor must be <= 0.5"
    kernel = functools.partial(_hash_build_kernel, table_size=table_size)
    return pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((keys.shape[0],), lambda i: (0,))],
        out_specs=[
            pl.BlockSpec((table_size,), lambda i: (0,)),
            pl.BlockSpec((table_size,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((table_size,), jnp.int64),
            jax.ShapeDtypeStruct((table_size,), jnp.int32),
        ],
        interpret=interpret,
    )(keys)


def _hash_probe_kernel(tkey_ref, trow_ref, probe_ref, out_ref, *,
                       table_size: int):
    """Vectorized linear-probing LOOKUP of one probe block against the
    table resident in VMEM: every lane walks its probe chain in lockstep
    until it hits its key (matched) or an empty slot (no match — open
    addressing guarantees the chain for a key is empty-terminated).
    Sentinel probes (NULL/dead) never match."""
    import jax.numpy as jnp

    tkey = tkey_ref[...]
    trow = trow_ref[...]
    probe = probe_ref[...]                     # [B] int64
    mask = table_size - 1
    h = jnp.asarray(_mix64(probe.view(jnp.uint64)), jnp.int64) & mask

    def step(state):
        disp, row, done = state
        slot = (h + disp) & mask
        k = tkey[slot]
        hit = (~done) & (k == probe)
        miss = (~done) & (k == _EMPTY)
        row = jnp.where(hit, trow[slot], row)
        return disp + 1, row, done | hit | miss

    init = (
        jnp.zeros(probe.shape, jnp.int32),
        jnp.full(probe.shape, -1, jnp.int32),
        probe == _EMPTY,
    )
    _, row, _ = jax.lax.while_loop(lambda s: jnp.any(~s[2]), step, init)
    out_ref[...] = row


def hash_probe_pallas(table_key, table_row, probe, block: int = 2048,
                      interpret: bool = False):
    """Probe the open-addressing table: returns [M] int32 matched build row
    ids (-1 = no match). Probe blocks stream through the grid while the
    table stays resident — one HBM pass over the probe, zero sorts
    anywhere (the sort+searchsorted replacement of the unique join)."""
    import jax.experimental.pallas as pl

    n = probe.shape[0]
    t = int(table_key.shape[0])
    assert n % block == 0, f"probe {n} must be a multiple of block {block}"
    kernel = functools.partial(_hash_probe_kernel, table_size=t)
    return pl.pallas_call(
        kernel,
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((t,), lambda i: (0,)),
            pl.BlockSpec((t,), lambda i: (0,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        interpret=interpret,
    )(table_key, table_row, probe)


# --- join probe: the searchsorted ladder as an explicit kernel ---------------


def _probe_block_kernel(build_ref, probe_ref, pos_ref, *, k: int,
                        iters: int):
    """Vectorized binary search of one probe block against the SORTED
    build keys resident in VMEM: `iters` halving steps, each a masked
    gather over the whole block (the searchsorted ladder of the sorted
    join probe, be/src/exec/join_hash_map.h's probe loop re-designed as a
    branch-free ladder the VPU runs in lockstep)."""
    build = build_ref[...]          # [K] int64, sorted, padded with +inf
    probe = probe_ref[...]          # [B] int64
    lo = jnp.zeros(probe.shape, jnp.int32)
    hi = jnp.full(probe.shape, k, jnp.int32)
    for _ in range(iters):          # static unroll: log2(K) steps
        mid = (lo + hi) // 2
        mv = build[jnp.clip(mid, 0, k - 1)]
        active = lo < hi            # converged lanes must stop moving
        go_right = (mv < probe) & active
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
    pos_ref[...] = lo               # first index with build[idx] >= probe


def probe_searchsorted_pallas(sorted_build, probe, block: int = 2048,
                              interpret: bool = False):
    """jnp.searchsorted(sorted_build, probe, side='left') as a Pallas grid
    kernel: the build side stays resident in VMEM while probe blocks
    stream through (one HBM pass over the probe). Flag-gated behind
    `SET join_probe_strategy = 'pallas_sorted'` (ops/join.py) — interpret
    mode off-TPU for correctness tests; on a TPU Mosaic does not lower it
    yet (int64 refs; see the module docstring)."""
    import jax.experimental.pallas as pl

    n = probe.shape[0]
    k = int(sorted_build.shape[0])
    assert n % block == 0, f"probe {n} must be a multiple of block {block}"
    iters = max(k, 1).bit_length()
    kernel = functools.partial(_probe_block_kernel, k=k, iters=iters)
    return pl.pallas_call(
        kernel,
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((k,), lambda i: (0,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        interpret=interpret,
    )(sorted_build, probe)
