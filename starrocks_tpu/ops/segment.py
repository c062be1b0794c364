"""Scatter-free segment reductions (the TPU aggregation substrate).

XLA lowers `jax.ops.segment_sum` & friends to scatter-add, which on TPU
serializes on duplicate indices — measured ~1000x slower than the matmul
formulation for the Q1-class shapes (millions of rows, few groups). This
module provides segment sum/min/max/count that never emit a scatter on the
hot paths; reference analog: the SIMD agg hash maps
(be/src/exec/aggregate/agg_hash_map.h) re-designed for the MXU.

Strategies, picked per dtype / group count / sortedness:

1. **One-hot matmul (MXU)** — small/medium group counts. Integer values are
   decomposed into 8-bit limbs, each limb column is summed per group with an
   f32 one-hot einsum whose per-block partial sums stay below 2^24 (exact in
   f32), then recombined with wrap-around int64 arithmetic. Two's-complement
   wrap-around makes the result EXACT mod 2^64 — the same overflow contract
   as a native int64 accumulator. Counts use a single limb.
2. **Broadcast-reduce** — tiny group counts, float values / min / max:
   out[g] = reduce(where(gid == g, vals, identity)); XLA fuses the compare
   into the reduction, no scatter, no materialized one-hot.
3. **Sorted prefix tricks** — group-sorted rows (the lexsort agg path,
   window partitions): sums become cumsum diffs at group boundaries found by
   searchsorted; min/max become a segmented associative scan read at the
   segment ends. All gathers, no scatters.
4. Fallback: jax.ops.segment_* (scatter) for shapes none of the above
   covers (e.g. huge unsorted group counts with float min/max).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import phase

_LIMB_BITS = 8
_LIMB_MASK = (1 << _LIMB_BITS) - 1
# per-block partial sums must stay exactly representable in f32:
# block * limb_max <= 2^24  ->  block <= 2^24 / 255  ->  32768 is safe.
_MAX_BLOCK = 32768


def _matmul_groups_max() -> int:
    from ..runtime.config import config

    return config.get("matmul_segsum_groups_max")


def _bcast_groups_max() -> int:
    from ..runtime.config import config

    return config.get("bcast_segreduce_groups_max")


def _block_of(n: int) -> int:
    """Largest power-of-two divisor of n, capped at _MAX_BLOCK."""
    return min(n & -n, _MAX_BLOCK)


def _onehot_blocked(gid, num_groups: int, block: int):
    """[nb, block, G+1] f32 one-hot; gid >= num_groups lands in the spill
    column which callers discard."""
    g = jnp.clip(jnp.asarray(gid, jnp.int32), 0, num_groups).reshape(-1, block)
    return (g[:, :, None] == jnp.arange(num_groups + 1, dtype=jnp.int32)).astype(
        jnp.float32
    )


@phase("limbs")
def _seg_sum_int_matmul(vals, gid, num_groups: int, nbits: int):
    """Exact (mod 2^64) integer segment sums on the MXU."""
    n = vals.shape[0]
    block = _block_of(n)
    nlimbs = max(1, (nbits + _LIMB_BITS - 1) // _LIMB_BITS)
    u = jnp.asarray(vals, jnp.uint64)
    limbs = jnp.stack(
        [
            ((u >> (_LIMB_BITS * j)) & _LIMB_MASK).astype(jnp.float32)
            for j in range(nlimbs)
        ],
        axis=-1,
    ).reshape(-1, block, nlimbs)
    oh = _onehot_blocked(gid, num_groups, block)
    # [nb, G+1, L] — each element an integer < 2^24, exact in f32
    part = jnp.einsum("nbg,nbl->ngl", oh, limbs)
    tot = jnp.sum(part.astype(jnp.uint64), axis=0)  # [G+1, L]
    out = jnp.zeros((num_groups + 1,), jnp.uint64)
    for j in range(nlimbs):
        out = out + (tot[:, j] << (_LIMB_BITS * j))
    return jnp.asarray(out[:num_groups], vals.dtype if vals.dtype != jnp.bool_
                       else jnp.int64)


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


def on_tpu() -> bool:
    """The one backend rule of the engine: Pallas kernels compile through
    Mosaic, the scatter-free segment strategies are `auto`'s choice and the
    planner's dense-aggregation domain is tight exactly when the default
    backend is a TPU. Any other backend (the CPU of the test suite)
    interprets the kernels and takes the scatter-friendly choices."""
    return jax.default_backend() == "tpu"


def _seg_sum_pallas(vals, gid, num_groups: int):
    """Float segment sums through the explicit Pallas kernel
    (ops/pallas_kernels.py): one-hot tiles in VMEM, partial sums on the MXU.
    Flag-gated via segment_strategy=pallas; interpret mode off-TPU keeps the
    path correctness-testable without hardware. f32 accumulation — callers
    gate exact (int/decimal) sums away from it. A row count that does not
    block-divide raises: an explicitly chosen strategy never quietly gives
    way to another."""
    n = vals.shape[0]
    block = min(n & -n, 2048)
    if block < 8:
        raise ValueError(
            f"segment_strategy=pallas needs a row count divisible by 8, "
            f"got {n}")
    from .pallas_kernels import segment_sum_pallas

    g = jnp.clip(jnp.asarray(gid, jnp.int32), 0, num_groups)
    out = segment_sum_pallas(
        g, jnp.asarray(vals, jnp.float32)[:, None], num_groups, block=block,
        interpret=not on_tpu(),
    )
    return jnp.asarray(out[:, 0], vals.dtype)


def _use_mxu() -> bool:
    """True when the scatter-free (matmul / broadcast / scan) strategies
    should be used.  They exist because TPU scatters serialize on duplicate
    indices; on the CPU backend a plain scatter is 100-1000x FASTER than the
    one-hot matmul (CPU run: 1.2M rows x 1024 groups = 1.1ms scatter vs >1s
    matmul), so `auto` picks by backend (`on_tpu`). `segment_strategy`
    config: auto | mxu | scatter (tests pin `mxu` to keep the strategy
    branches covered on CPU)."""
    from ..runtime.config import config

    if not config.get("enable_scatter_free_segments"):
        return False
    s = config.get("segment_strategy")
    if s == "auto":
        return on_tpu()
    # "pallas" only reroutes float sums; every other reduction must keep
    # its scatter-free strategy (degrading them to scatters would make the
    # pallas A/B benchmark measure scatter serialization instead)
    return s in ("mxu", "pallas")


@phase("segments")
def seg_sum(vals, gid, num_groups: int, *, sorted_gid: bool = False,
            nbits: int = 64):
    """Segment sum without scatters where possible.

    gid must map dead rows OUT of [0, num_groups). `nbits` bounds the value
    bit-width for integer inputs (e.g. 1 for 0/1 liveness counts) — fewer
    limbs, less HBM traffic. Results match jax.ops.segment_sum exactly for
    ints; float results differ only by reduction order.
    """
    vals = jnp.asarray(vals)
    if vals.dtype == jnp.bool_:
        vals = jnp.asarray(vals, jnp.int64)
    if num_groups == 1:
        # global aggregate: one fused masked reduction, no scatter / one-hot
        # on ANY backend (the gid==0 compare folds away when gid is the
        # constant zeros of the no-group-key path)
        m = jnp.asarray(gid, jnp.int32) == 0
        return jnp.sum(jnp.where(m, vals, jnp.zeros((), vals.dtype)),
                       keepdims=True)
    from ..runtime.config import config as _cfg

    if (_cfg.get("segment_strategy") == "pallas"
            and not jnp.issubdtype(vals.dtype, jnp.integer)
            and num_groups <= _matmul_groups_max()):
        return _seg_sum_pallas(vals, gid, num_groups)
    if _use_mxu():
        if jnp.issubdtype(vals.dtype, jnp.integer):
            v64 = jnp.asarray(vals, jnp.int64)
            if (num_groups <= _matmul_groups_max()
                    and _block_of(v64.shape[0]) >= 512):
                return _seg_sum_int_matmul(v64, gid, num_groups, nbits)
            if sorted_gid:
                return _seg_sum_sorted(v64, gid, num_groups)
        else:
            if num_groups <= _bcast_groups_max():
                return _seg_sum_float_bcast(vals, gid, num_groups)
            if sorted_gid:
                return _seg_sum_sorted_float(vals, gid, num_groups)
    return jax.ops.segment_sum(vals, gid, num_segments=num_groups,
                               indices_are_sorted=sorted_gid)


def seg_count(live, gid, num_groups: int, *, sorted_gid: bool = False):
    """Per-group count of live rows (single-limb matmul / cumsum)."""
    return seg_sum(jnp.asarray(live, jnp.int64), gid, num_groups,
                   sorted_gid=sorted_gid, nbits=1)


@phase("segments")
def _seg_minmax(vals, gid, num_groups: int, is_min: bool, identity,
                sorted_gid: bool):
    vals = jnp.asarray(vals)
    if num_groups == 1:
        m = jnp.asarray(gid, jnp.int32) == 0
        masked = jnp.where(m, vals, jnp.asarray(identity, vals.dtype))
        return (jnp.min if is_min else jnp.max)(masked, keepdims=True)
    if _use_mxu():
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
