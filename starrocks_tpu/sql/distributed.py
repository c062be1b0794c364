"""Distributed physical planning: logical plan -> one SPMD shard_map program.

Reference behavior: the fragment/exchange machinery (SURVEY §2.4) — the FE
cuts plans into fragments at exchange boundaries and schedules N instances
across BEs (qe/CoordinatorPreprocessor.java:70, scheduler/dag/ExecutionDAG);
BEs shuffle via bRPC transmit_chunk. The TPU re-design compiles the WHOLE
distributed plan into a single jitted shard_map over the ICI mesh:

- big tables are row-sharded over the mesh (the tablet->BE assignment
  analog); small tables are replicated to every shard (colocate-by-copy);
- join strategies: probe-sharded x build-replicated = local broadcast join
  (no collective); sharded x sharded = hash-shuffle both sides
  (lax.all_to_all) then local join — HASH_PARTITIONED exchange;
- aggregation over sharded input: colocate COMPLETE when the input is
  hash-placed on a subset of the group keys; else two-phase — local PARTIAL,
  then all_gather+FINAL (low-cardinality) or an all_to_all SHUFFLE of the
  partial states with per-shard FINAL (high-cardinality, by NDV estimate);
- ORDER BY+LIMIT = per-shard TopN, compact, gather top-k only; full ORDER BY
  = range exchange by sampled splitters + local sort (shards end globally
  ordered); PARTITION BY windows shuffle by partition key and run locally;
  unpartitioned windows and bare LIMIT still gather to replicated.

Every node returns (chunk, mode) with mode one of REPLICATED, SHARDED,
RANGE_SHARDED (sharded + globally ordered across the axis), or
("hash", col) (sharded by the standard splitmix64 recipe on col — the
colocate-placement token). Checks carry per-shard true counts as [1]-arrays
(out_spec P('d')) so the host overflow-recompile loop sees the max across
shards.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import types as T
from ..column.column import Field, pad_capacity
from ..exprs.compile import dict_predicate_log
from ..exprs.ir import Col, Lit
from ..ops import (
    INNER, LEFT_ANTI, LEFT_OUTER, LEFT_SEMI,
    filter_chunk, hash_aggregate, hash_join_expand, hash_join_unique,
    limit_chunk, project, sort_chunk,
)
from ..ops.aggregate import (
    COMPLETE, FINAL, PARTIAL, decomposable, final_agg_exprs,
)
from ..ops.common import INDEX_METHOD, compact, eval_keys
from ..ops.sort import _descending
from ..ops.window import window_op
from ..parallel.exchange import (
    all_gather_chunk, range_partition_chunk, shuffle_chunk,
)
from ..parallel.mesh import DATA_AXIS
from .analyzer import _conjuncts
from .logical import (
    LAggregate, LFilter, LJoin, LLimit, LProject, LScan, LSort, LUnion,
    LUnnest, LWindow, LogicalPlan, walk_plan,
)
from .optimizer import and_all
from .physical import (
    Caps, PlanError, _equi_pair, _key_bit_width, join_side_estimates,
    plan_scopes, scope_name, scope_table, shrink_capacity, unique_sets,
)

SHARDED = "sharded"
REPLICATED = "replicated"
# sharded AND globally ordered across the device axis (range exchange +
# local sort): a tiled all_gather concatenates shards into sorted order
RANGE_SHARDED = "range_sharded"

# tables smaller than this are replicated rather than sharded
SHARD_THRESHOLD_ROWS = 100_000
# estimated group count above which two-phase aggregation shuffles partial
# states by group key (each shard finalizes its own key range) instead of
# all_gathering them (every shard redundantly finalizes all groups) —
# the reference's HASH_PARTITIONED vs GATHER enforcer choice
# (fe sql/optimizer/ChildOutputPropertyGuarantor.java)
SHUFFLE_AGG_MIN_GROUPS = 32_768


def _default_bucket_cap(capacity: int, n_shards: int) -> int:
    """Default per-destination exchange bucket capacity: even split of the
    input capacity with ~2x skew headroom (n//2 destinations' worth)."""
    return pad_capacity(capacity // max(n_shards // 2, 1))


def estimated_group_ndv(p: LAggregate, catalog):
    """Upper bound on GROUP BY cardinality: product over group keys of the
    exact per-column distinct counts (collected once per column in the
    catalog — the ANALYZE analog), capped by the child's estimated row
    count (the tuple NDV can't exceed the rows feeding the agg; the old
    (max-min+1) range product over-estimated sparse/multi-key groups by
    orders of magnitude and pushed plans into shuffle-final aggregation
    with huge seeded capacities). None when any key is a non-Col expression
    or unresolvable (then the planner stays BROADCAST)."""
    if not p.group_by:
        return 0
    from .optimizer import col_origin, estimate_rows

    total = 1
    for _, e in p.group_by:
        if not isinstance(e, Col):
            return None
        origin = col_origin(p.child, e.name)
        if origin is None:
            return None
        t = catalog.get_table(origin[0])
        if t is None:
            return None
        ndv = t.column_ndv(origin[1])
        if ndv is None:
            return None
        total *= max(int(ndv), 1)
        if total > (1 << 40):
            break
    return min(total, int(max(estimate_rows(p.child, catalog), 1.0)))


def _single_sort_rank(chunk, sort_keys):
    """One totally-ordered per-row array encoding a single-key ORDER BY
    (asc/desc + NULLS FIRST/LAST), for the range-partition exchange; None
    when the sort is multi-key (ties at a splitter boundary could split a
    secondary-order run across shards) or the key dtype is unsupported.
    Caveat: NULLs share a rank with the dtype's extreme value, so a real
    INT64_MIN/MAX (or +/-inf) key can interleave with NULLs at a shard
    boundary — same class of caveat as _descending's INT_MIN note."""
    if len(sort_keys) != 1:
        return None
    expr, asc, nulls_first = sort_keys[0]
    (k,) = eval_keys(chunk, (expr,))
    d = k.data
    if d.dtype == jnp.bool_:
        d = jnp.asarray(d, jnp.int8)
    if jnp.issubdtype(d.dtype, jnp.unsignedinteger):
        return None
    rank = d if asc else _descending(d)
    if k.valid is not None:
        if jnp.issubdtype(rank.dtype, jnp.floating):
            sentinel = -jnp.inf if nulls_first else jnp.inf
        else:
            info = jnp.iinfo(rank.dtype)
            sentinel = info.min if nulls_first else info.max
        rank = jnp.where(k.valid, rank, jnp.asarray(sentinel, rank.dtype))
    return rank


class DistCompiled:
    def __init__(self, fn, scans, scan_modes, checks_meta, out_names, n_shards,
                 scopes=None, compactions=None, exchanges=None,
                 segment_sums=None, dict_predicates=None):
        self.fn = fn
        self.scans = scans  # list[(table, alias, columns)]
        self.scan_modes = scan_modes  # list[SHARDED|REPLICATED]
        self.checks_meta = checks_meta
        self.out_names = out_names
        self.n_shards = n_shards
        # scope number -> node repr (physical.scope_table)
        self.scopes = scopes or {}
        # what fn's trace found out about the program, filled while it
        # traces (as physical.Compiled.compactions is): `compactions` by
        # key (`limit_<n>`, `topn_<n>`: rows in, slots out, index method),
        # `exchanges` in program order (parallel/exchange.py `_shape`),
        # `segment_sums` by aggregate scope, `/partial` and `/final` apart
        # (physical.Compiled.segment_sums), `dict_predicates` by plan-node
        # scope (physical.Compiled.dict_predicates)
        self.compactions = {} if compactions is None else compactions
        self.exchanges = [] if exchanges is None else exchanges
        self.segment_sums = {} if segment_sums is None else segment_sums
        self.dict_predicates = ({} if dict_predicates is None
                                else dict_predicates)


def plan_scan_modes(plan: LogicalPlan, catalog) -> dict:
    """Decide placement per scan: replicate small tables; big tables shard —
    by HASH of a single int distribution column when declared (enabling
    colocate joins: the host placement uses the same splitmix64 bucketing as
    the device shuffle), else by row range."""
    modes = {}

    def rec(p):
        if isinstance(p, LScan):
            t = catalog.get_table(p.table)
            rows = t.row_count if t is not None else 0
            if rows < SHARD_THRESHOLD_ROWS:
                modes[id(p)] = REPLICATED
            else:
                mode = SHARDED
                dist = getattr(t, "distribution", ())
                if len(dist) == 1 and dist[0] in p.columns:
                    f = t.schema.field(dist[0])
                    if f.type.is_integer:
                        mode = ("hash", f"{p.alias}.{dist[0]}")
                modes[id(p)] = mode
        for c in p.children:
            rec(c)

    rec(plan)
    return modes


def _is_dist(mode) -> bool:
    return mode != REPLICATED


def _hash_col(mode):
    return mode[1] if isinstance(mode, tuple) and mode[0] == "hash" else None


def compile_distributed(
    plan: LogicalPlan, catalog, caps: Caps, n_shards: int,
    axis: str = DATA_AXIS, scan_modes: dict | None = None,
    recorder=None, fragment=None,
) -> DistCompiled:
    """recorder: optional fragments.ExchangeRecorder — `note`d immediately
    before every collective with the plan edge it implements (the fragment-IR
    annotation source; zero drift from the lowering by construction).
    fragment: optional fragments.Fragment — compile only the subtree rooted
    at fragment.root, resolving fragment.boundary nodes from the extra `bnd`
    argument of step instead of emitting them (the per-fragment program)."""
    scan_modes = scan_modes or plan_scan_modes(plan, catalog)
    scans: list = []
    node_ord: dict = {}
    # deterministic pre-order ordinals: capacity/check keys (shufL_3,
    # agg_5, ...) must be identical whether the plan compiles as one
    # monolithic program or one fragment at a time — fragments share the
    # adaptive capacity state and the partial-state cache under these keys
    for _n in walk_plan(plan):
        node_ord.setdefault(_n, len(node_ord))

    def ordinal(p) -> int:
        return node_ord.setdefault(p, len(node_ord))

    scopes = plan_scopes(plan)
    compactions: dict = {}
    exchanges: list = []
    segment_sums: dict = {}
    dict_predicates: dict = {}
    all_gather = functools.partial(all_gather_chunk, axis=axis, log=exchanges)

    if recorder is not None:
        note = recorder.note
    else:
        def note(*a, **k):
            return None

    root_node = plan if fragment is None else fragment.root

    scan_index: dict = {}
    scan_mode_list: list = []

    def collect(p):
        if isinstance(p, LScan):
            if id(p) not in scan_index:
                scan_index[id(p)] = len(scans)
                scans.append((p.table, p.alias, p.columns))
                scan_mode_list.append(scan_modes.get(id(p), REPLICATED))
        for c in p.children:
            collect(c)

    collect(plan)

    def gather(chunk, mode):
        if mode == REPLICATED:
            return chunk
        return all_gather(chunk)  # range- and hash-sharded alike

    def step(inputs, bnd=()):
        """Traced SPMD program; all mutable trace state lives inside (see
        compile_plan) so cached jitted versions retrace safely. Overflow
        checks return as {key: [1]-array} merged across shards by the host.
        `bnd` carries fragment-boundary chunks (upstream fragment outputs)
        positionally; empty for monolithic compiles."""
        emit_memo: dict = {}
        checks: dict = {}
        exchanges.clear()  # a retrace (the auditor's, a new input layout)
        dict_predicates.clear()

        def emit(p):
            if p in emit_memo:
                return emit_memo[p]
            # the operator's name on its device operations, and on the
            # dictionary predicates it evaluates, as in physical.compile_plan
            name = scope_name(scopes, p)
            with jax.named_scope(name), dict_predicate_log(dict_predicates,
                                                           name):
                out = _emit(p)
            emit_memo[p] = out
            return out

        def _emit(p):
            if fragment is not None and p in fragment.boundary:
                # fragment edge: the subtree below p ran in an upstream
                # fragment; resume from its output in the recorded mode
                # (checked FIRST so the sink fragment — root == plan ∈
                # boundary — resolves to the boundary, not a re-emission)
                slot, bmode = fragment.boundary[p]
                return bnd[slot], bmode
            if isinstance(p, LScan):
                i = scan_index[id(p)]
                return inputs[i], scan_mode_list[i]
            if isinstance(p, LFilter):
                c, m = emit(p.child)
                return filter_chunk(c, p.predicate), m
            if isinstance(p, LProject):
                c, m = emit(p.child)
                hc = _hash_col(m)
                if hc is not None:
                    # keep colocate info only if the hash column passes through
                    m = SHARDED
                    for n, e in p.exprs:
                        if isinstance(e, Col) and e.name == hc:
                            m = ("hash", n)
                            break
                return (
                    project(c, [e for _, e in p.exprs], [n for n, _ in p.exprs]),
                    m,
                )
            if isinstance(p, LWindow):
                return emit_window(p)
            if isinstance(p, LUnnest):
                return emit_unnest(p)
            if isinstance(p, LSort):
                return emit_sort(p)
            if isinstance(p, LLimit):
                c, m = emit(p.child)
                if _is_dist(m) and p.limit is not None:
                    # push the LIMIT through the exchange: any row in the
                    # global first limit+offset is within its shard's first
                    # limit+offset (holds for range-ordered shards too), so
                    # pre-limit + compact and gather only ~k*shards rows
                    k = p.limit + p.offset
                    c = limit_chunk(c, k, 0)
                    kcap = pad_capacity(k)
                    if kcap < c.capacity:
                        # live <= k: no overflow
                        c, _ = compact_to(c, f"limit_{ordinal(p)}", kcap)
                if _is_dist(m):
                    note(p, 0, p.child, "gather", (), REPLICATED, "limit",
                         m, c)
                return limit_chunk(gather(c, m), p.limit, p.offset), REPLICATED
            if isinstance(p, LUnion):
                from ..ops.setops import union_all

                out, m = emit(p.inputs[0])
                if _is_dist(m):
                    note(p, 0, p.inputs[0], "gather", (), REPLICATED,
                         "rows", m, out)
                out = gather(out, m)
                for i, child in enumerate(p.inputs[1:], start=1):
                    c2, m2 = emit(child)
                    if _is_dist(m2):
                        note(p, i, child, "gather", (), REPLICATED,
                             "rows", m2, c2)
                    out = union_all(out, gather(c2, m2))
                return out, REPLICATED
            if isinstance(p, LAggregate):
                return emit_agg(p)
            if isinstance(p, LJoin):
                return emit_join(p)
            raise PlanError(f"cannot compile {type(p).__name__} distributed")

        def compact_to(c, key: str, cap: int):
            """(chunk at `cap` slots, its live rows on this shard): a caller
            that cannot bound the rows puts the count in `checks[key]`."""
            compactions[key] = {"cap": c.capacity, "out_cap": cap,
                                "method": INDEX_METHOD}
            return compact(c, cap)

        def shrink(c, mode, key: str, est: float):
            """physical.compile_plan's `maybe_compact`, a shard: `est` live
            rows over all shards are `est / n_shards` here where the chunk
            is distributed. Dead slots only, so `mode` stands."""
            if _is_dist(mode):
                est = est / n_shards
            cap = shrink_capacity(caps, key, c.capacity, est)
            if cap is None:
                return c
            c, live = compact_to(c, key, cap)
            checks[key] = live[None]
            return c

        def _emit_ctrs(p, ctrs, dist: bool):
            """'~ctr_' profile counters ride the checks channel, whose host
            merge takes the MAX across shards (overflow semantics). A
            sharded stage's per-shard counts must SUM instead — psum them
            here inside the traced program, so every shard reports the
            global total and the host max is that total. Replicated stages
            compute the same value on every shard; emit as-is."""
            for nm, v in ctrs.items():
                if dist:
                    v = jax.lax.psum(v, axis)
                checks[f"~ctr_{nm}@{ordinal(p)}"] = v[None]

        def emit_window(p: LWindow):
            """PARTITION BY windows are independent per partition, so a
            sharded input shuffles by partition key and each shard computes
            its own partitions locally — no whole-table gather. Unpartitioned
            windows (global ranks/running totals) still need the gather."""
            c, m = emit(p.child)

            def win(chunk, dist: bool):
                ctrs: dict = {}
                out = window_op(chunk, p.partition_by, p.order_by, p.funcs,
                                limit_spec=p.limit, counters=ctrs)
                _emit_ctrs(p, ctrs, dist)
                return out

            if not p.partition_by or not _is_dist(m):
                if _is_dist(m):
                    note(p, 0, p.child, "gather", (), REPLICATED,
                         "rows", m, c)
                c = gather(c, m)
                return win(c, False), REPLICATED
            hc = _hash_col(m)
            # hash column among the partition keys => every partition is
            # wholly on one shard already (subset colocation rule)
            aligned = hc is not None and any(
                isinstance(e, Col) and e.name == hc for e in p.partition_by
            )
            out_mode = m if aligned else SHARDED
            if not aligned:
                if len(p.partition_by) == 1 and isinstance(p.partition_by[0], Col):
                    out_mode = ("hash", p.partition_by[0].name)
                key = f"win_{ordinal(p)}"
                bcap = caps.get(key, _default_bucket_cap(c.capacity, n_shards))
                note(p, 0, p.child, "hash", tuple(p.partition_by), out_mode,
                     "rows", m, c)
                c, mxb = shuffle_chunk(
                    c, tuple(p.partition_by), axis, n_shards, bcap,
                    log=exchanges, check=key,
                )
                checks[key] = mxb[None]
            return win(c, True), out_mode

        def emit_sort(p: LSort):
            c, m = emit(p.child)

            def srt(chunk, limit, dist: bool):
                ctrs: dict = {}
                out = sort_chunk(chunk, p.keys, limit, counters=ctrs)
                _emit_ctrs(p, ctrs, dist)
                return out

            if not _is_dist(m):
                return srt(c, p.limit, False), REPLICATED
            if p.limit is not None:
                # distributed TopN: per-shard TopN (threshold-pruned when the
                # keys pack), compact to ~limit rows, gather only k*shards
                # rows, final TopN at the coordinator shard — the LIMIT+ORDER
                # pushed through the exchange (chunks_sorter_topn.h analog)
                local = srt(c, p.limit, True)
                kcap = pad_capacity(p.limit)
                if kcap < local.capacity:
                    # live <= limit: no overflow
                    local, _ = compact_to(local, f"topn_{ordinal(p)}", kcap)
                note(p, 0, p.child, "gather", (), REPLICATED, "topn",
                     m, local)
                gathered = all_gather(local)
                return sort_chunk(gathered, p.keys, p.limit), REPLICATED
            rank = _single_sort_rank(c, p.keys)
            if rank is None:
                note(p, 0, p.child, "gather", (), REPLICATED, "rows", m, c)
                return sort_chunk(gather(c, m), p.keys, None), REPLICATED
            # full distributed sort: range exchange by sampled splitters,
            # then local sort — shards end range-ordered, so the final
            # tiled all_gather concatenates into global order
            key = f"sort_{ordinal(p)}"
            bcap = caps.get(key, _default_bucket_cap(c.capacity, n_shards))
            note(p, 0, p.child, "range", (p.keys[0][0],), RANGE_SHARDED,
                 "rows", m, c)
            part, mxb = range_partition_chunk(
                c, rank, axis, n_shards, bcap, log=exchanges, check=key)
            checks[key] = mxb[None]
            return sort_chunk(part, p.keys, None), RANGE_SHARDED

        def emit_agg(p: LAggregate):
            def aggregate(chunk, group_by, aggs, cap, mode=COMPLETE,
                          **kwargs):
                """ops.hash_aggregate, its batch of segment sums noted under
                the node's scope (a two-phase node notes two)."""
                info: dict = {}
                out = hash_aggregate(chunk, group_by, aggs, cap, mode=mode,
                                     sums_info=info, **kwargs)
                if info:
                    name = scope_name(scopes, p)
                    segment_sums[name if mode == COMPLETE
                                 else f"{name}/{mode}"] = info
                return out

            c, m = emit(p.child)
            key = f"agg_{ordinal(p)}"
            agg_default = 1024 if p.group_by else 1
            if m == REPLICATED:
                kwargs = {}
                if any(a.fn == "array_agg" for _, a in p.aggs):
                    akey = f"aggarr_{ordinal(p)}"
                    aux: dict = {}
                    kwargs = {"arr_cap": caps.get(akey, 256),
                              "aux_checks": aux}
                out, ng = aggregate(c, p.group_by, p.aggs,
                                         caps.get(key, agg_default), **kwargs)
                checks[key] = ng[None]
                if kwargs:
                    checks[akey] = aux["array_agg_max"][None]
                return out, REPLICATED
            final_group_by = tuple((n, Col(n)) for n, _ in p.group_by)
            est = estimated_group_ndv(p, catalog)
            hc = _hash_col(m)
            hash_out = next(
                (n for n, e in p.group_by
                 if isinstance(e, Col) and e.name == hc),
                None,
            ) if hc is not None else None
            if hash_out is not None:
                # input hash-placed on a SUBSET of the group keys: every
                # group lives entirely on one shard, so a single COMPLETE
                # local agg is exact with zero collectives (colocate agg).
                # Seed capacity from the NDV estimate (per-shard share, 2x
                # skew headroom) so typical runs compile once.
                default = 1024 if est is None else pad_capacity(
                    int(min(est * 2 // n_shards + 1024, c.capacity))
                )
                out, ng = aggregate(c, p.group_by, p.aggs,
                                         caps.get(key, default))
                checks[key] = ng[None]
                return out, ("hash", hash_out)
            if not decomposable(p.aggs):
                # holistic aggregates (percentile family) need every group
                # value in one place and the input is not colocated on the
                # group keys: gather rows, aggregate COMPLETE.
                note(p, 0, p.child, "gather", (), REPLICATED, "rows", m, c)
                gathered = all_gather(c)
                kwargs = {}
                if any(a.fn == "array_agg" for _, a in p.aggs):
                    akey = f"aggarr_{ordinal(p)}"
                    aux: dict = {}
                    kwargs = {"arr_cap": caps.get(akey, 256),
                              "aux_checks": aux}
                out, ng = aggregate(gathered, p.group_by, p.aggs,
                                         caps.get(key, agg_default), **kwargs)
                checks[key] = ng[None]
                if kwargs:
                    checks[akey] = aux["array_agg_max"][None]
                return out, REPLICATED
            if est is not None and est > SHUFFLE_AGG_MIN_GROUPS:
                # high cardinality: shuffle partial states by group key so
                # each shard finalizes only its own key range (SHUFFLE-final).
                # Seed the partial capacity from the estimate (bounded by the
                # input capacity) — the 1024 default would always overflow
                cap = caps.get(key, pad_capacity(int(min(est, c.capacity))))
                part, png = aggregate(
                    c, p.group_by, p.aggs, cap, mode=PARTIAL
                )
                checks[key] = png[None]
                bkey = f"aggbkt_{ordinal(p)}"
                bcap = caps.get(
                    bkey, pad_capacity(max(cap // max(n_shards // 2, 1), 16))
                )
                key_cols = tuple(Col(n) for n, _ in p.group_by)
                # output is hash-placed on the (single) group column's
                # values with the standard shuffle recipe -> colocate-able
                out_mode = (
                    ("hash", p.group_by[0][0]) if len(p.group_by) == 1
                    else SHARDED
                )
                note(p, 0, p.child, "hash", key_cols, out_mode, "partial",
                     m, part)
                merged, mxb = shuffle_chunk(
                    part, key_cols, axis, n_shards, bcap,
                    log=exchanges, check=bkey)
                checks[bkey] = mxb[None]
                # final capacity = received capacity: group count there is
                # bounded by received rows, so the final phase cannot overflow
                out, _ng = aggregate(
                    merged, final_group_by, final_agg_exprs(p.aggs),
                    n_shards * bcap, mode=FINAL,
                )
                return out, out_mode
            # two-phase: local partial -> all_gather -> final
            cap = caps.get(key, agg_default)
            part, png = aggregate(c, p.group_by, p.aggs, cap, mode=PARTIAL)
            note(p, 0, p.child, "gather", (), REPLICATED, "partial", m, part)
            merged = all_gather(part)
            out, ng = aggregate(
                merged, final_group_by, final_agg_exprs(p.aggs), cap, mode=FINAL
            )
            # both partial and final counts must fit the capacity
            checks[key] = jnp.maximum(png, ng)[None]
            return out, REPLICATED

        def emit_unnest(p: LUnnest):
            from ..ops.unnest import unnest_op

            c, m = emit(p.child)
            key = f"unnest_{ordinal(p)}"
            cap = caps.get(key, pad_capacity(c.capacity * 4))
            out, total = unnest_op(c, p.expr, p.out_name, cap)
            checks[key] = total[None]
            return out, m

        def emit_join(p: LJoin):
            lc, lm = emit(p.left)
            rc, rm = emit(p.right)
            # pre-degrade modes: what emit(child) actually returned — the
            # fragment-boundary mode a consumer fragment resumes with (it
            # re-applies the degrade/claim-drop rules below itself)
            lm0, rm0 = lm, rm
            # joins reorder rows: a range-ordered input degrades to plain
            # sharded (placement survives, global ordering does not)
            lm = SHARDED if lm == RANGE_SHARDED else lm
            rm = SHARDED if rm == RANGE_SHARDED else rm
            lcols = frozenset(p.left.output_names())
            rcols = frozenset(p.right.output_names())

            probe_keys, build_keys, residual = [], [], []
            for conj in (_conjuncts(p.condition) if p.condition is not None else []):
                pair = _equi_pair(conj, lcols, rcols)
                if pair is not None:
                    probe_keys.append(pair[0])
                    build_keys.append(pair[1])
                else:
                    residual.append(conj)

            kind = {
                "inner": INNER, "left": LEFT_OUTER, "semi": LEFT_SEMI,
                "anti": LEFT_ANTI, "cross": INNER,
            }[p.kind]

            if not probe_keys:
                probe_keys, build_keys = [Lit(0)], [Lit(0)]
                bit_widths = (2,)
                unique = False
                if _is_dist(lm) and _is_dist(rm):
                    # shuffling a constant key would funnel everything onto one
                    # shard; gather the build side and cross-join locally
                    note(p, 1, p.right, "broadcast", (), REPLICATED,
                         "rows", rm0, rc)
                    rc = all_gather(rc)
                    rm = REPLICATED
            else:
                from .physical import choose_key_packing

                bit_widths, residual, unique = choose_key_packing(
                    p, probe_keys, build_keys, residual, catalog
                )
                # equal strings must carry equal codes before any
                # per-side routing (shuffle/colocate placement)
                from ..ops.join import align_chunk_dicts

                lc2, rc2 = align_chunk_dicts(lc, rc, probe_keys, build_keys)
                if lc2 is not lc or rc2 is not rc:
                    # remapped codes no longer match the host hash placement
                    # of a colocate scan: drop placement claims, force the
                    # generic shuffle on the merged codes
                    lc, rc = lc2, rc2
                    lm = SHARDED if _is_dist(lm) else lm
                    rm = SHARDED if _is_dist(rm) else rm
                if _is_dist(lm) and _is_dist(rm):
                    # dict-typed EXPRESSION keys (upper(k) etc.) build fresh
                    # per-side dicts whose codes can't be aligned at the
                    # column level above — per-side shuffle routing would
                    # send equal strings to different shards. Gather the
                    # build side instead: the local join kernel aligns
                    # evaluated keys itself (pack_key_pair).
                    pks_e = eval_keys(lc, tuple(probe_keys))
                    bks_e = eval_keys(rc, tuple(build_keys))
                    for pe, be, pk_x, bk_x in zip(
                            pks_e, bks_e, probe_keys, build_keys):
                        if ((pe.dict is not None or be.dict is not None)
                                and not (isinstance(pk_x, Col)
                                         and isinstance(bk_x, Col))):
                            note(p, 1, p.right, "broadcast", (), REPLICATED,
                                 "rows", rm0, rc)
                            rc = all_gather(rc)
                            rm = REPLICATED
                            break

            # build-side runtime filter on the probe; with a sharded build
            # the local summaries merge across shards — gathered min/max for
            # the range filter, a bitwise OR (ops/join._or_across_shards)
            # for the dense bitmap AND the bloom bitset (the global-RF
            # collective; neither uses pmin/pmax, see ops/join.py). Strategy
            # ladder matches the single-chip compiler: dense > bloom >
            # min/max per `runtime_filter_strategy`.
            from ..runtime.config import config as _cfg
            from ..ops.join import bloom_filter_mask, runtime_filter_mask
            from .optimizer import estimate_rows
            from .physical import (
                bloom_rf_bits, bloom_rf_useful, dense_rf_range,
                rf_strategy_of,
            )

            strategy = rf_strategy_of(_cfg)
            exact_rf = False  # as in compile_plan: dense, or uncapped bloom
            if p.kind in ("inner", "semi", "cross") and probe_keys and not (
                len(probe_keys) == 1 and isinstance(probe_keys[0], Lit)
            ) and strategy != "off":
                rf_axis = axis if _is_dist(rm) else None
                dr = (dense_rf_range(p.left, p.right, probe_keys, build_keys,
                                     catalog)
                      if strategy == "auto" else None)
                bloom = None
                if dr is None and (strategy == "bloom" or (
                        strategy == "auto"
                        and bloom_rf_useful(p, probe_keys, build_keys,
                                            catalog))):
                    bloom = bloom_rf_bits(estimate_rows(p.right, catalog),
                                          _cfg.get("rf_bloom_max_bits"))
                n0 = lc.num_rows()
                if dr is None and bloom is not None:
                    bits, exact_rf = bloom
                    lc = lc.and_sel(bloom_filter_mask(
                        lc, rc, tuple(probe_keys), tuple(build_keys),
                        bit_widths, rf_axis, bits=bits))
                    # replicated on every shard: host max-merge = the value
                    checks[f"~ctr_rf_bloom_bits@{ordinal(p)}"] = (
                        jnp.asarray(bits, jnp.int64)[None])
                else:
                    exact_rf = dr is not None
                    lc = lc.and_sel(runtime_filter_mask(
                        lc, rc, tuple(probe_keys), tuple(build_keys),
                        bit_widths, rf_axis, dense_range=dr))
                pruned = n0 - lc.num_rows()
                if _is_dist(lm):
                    # per-shard prune counts SUM to the global total (the
                    # round-6 counter convention: psum in-program so the
                    # host max IS the cross-shard sum)
                    pruned = jax.lax.psum(pruned, axis)
                checks[f"~ctr_rf_rows_pruned@{ordinal(p)}"] = pruned[None]

            # the sides hold few live rows in many slots now (filters below,
            # the runtime filter above), and all that follows is priced per
            # slot: the shuffle's pack, the build's sort, the probe's search,
            # the payload gathers, the consumer at the join's capacity.
            # Shrink them here, BEFORE a side is shuffled, by the one-chip
            # compiler's rule (physical.shrink_capacity)
            est_l, est_r = join_side_estimates(p, catalog, exact_rf)
            lc = shrink(lc, lm, f"shrink_{ordinal(p)}l", est_l)
            rc = shrink(rc, rm, f"shrink_{ordinal(p)}r", est_r)

            # --- distribution strategy ---
            def align_pos(mode, keys):
                """Index of the equi-key pair this side is hash-placed on
                (subset colocation: matching rows agree on ALL equi keys, so
                placement by any ONE equated column keeps them together)."""
                hc = _hash_col(mode)
                if hc is None:
                    return None
                for i, k in enumerate(keys):
                    if isinstance(k, Col) and k.name == hc:
                        return i
                return None

            if _is_dist(lm) and _is_dist(rm):
                li = align_pos(lm, probe_keys)
                ri = align_pos(rm, build_keys)

                def shuffle_side(chunk, keys_, key_name):
                    cap_k = caps.get(
                        key_name, _default_bucket_cap(chunk.capacity, n_shards)
                    )
                    out, mx = shuffle_chunk(
                        chunk, tuple(keys_), axis, n_shards, cap_k, bit_widths,
                        log=exchanges, check=key_name,
                    )
                    checks[key_name] = mx[None]
                    return out

                def shuf_mode(keys_):
                    # post-shuffle placement: hash-placed on the single Col
                    # key (colocate token) or plain sharded otherwise
                    if len(keys_) == 1 and isinstance(keys_[0], Col):
                        return ("hash", keys_[0].name)
                    return SHARDED

                # colocate when both sides sit on the same equated pair; a
                # single aligned side pulls the other to ITS placement
                # (shuffle by just the equated column); else shuffle both
                # sides by the full key tuple
                if li is not None and ri == li:
                    anchor = li
                elif li is not None:
                    ks = [build_keys[li]]
                    note(p, 1, p.right, "hash", tuple(ks), shuf_mode(ks),
                         "rows", rm0, rc)
                    rc = shuffle_side(rc, ks, f"shufR_{ordinal(p)}")
                    anchor = li
                elif ri is not None:
                    ks = [probe_keys[ri]]
                    note(p, 0, p.left, "hash", tuple(ks), shuf_mode(ks),
                         "rows", lm0, lc)
                    lc = shuffle_side(lc, ks, f"shufL_{ordinal(p)}")
                    anchor = ri
                else:
                    note(p, 0, p.left, "hash", tuple(probe_keys),
                         shuf_mode(probe_keys), "rows", lm0, lc)
                    lc = shuffle_side(lc, probe_keys, f"shufL_{ordinal(p)}")
                    note(p, 1, p.right, "hash", tuple(build_keys),
                         shuf_mode(build_keys), "rows", rm0, rc)
                    rc = shuffle_side(rc, build_keys, f"shufR_{ordinal(p)}")
                    anchor = 0 if len(probe_keys) == 1 else None
                if anchor is not None and isinstance(probe_keys[anchor], Col):
                    out_mode = ("hash", probe_keys[anchor].name)
                else:
                    out_mode = SHARDED
            elif _is_dist(rm):  # probe replicated, build sharded -> gather build
                note(p, 1, p.right, "broadcast", (), REPLICATED,
                     "rows", rm0, rc)
                rc = all_gather(rc)
                out_mode = REPLICATED if lm == REPLICATED else lm
            else:
                # build replicated: local (broadcast) join; output follows probe
                out_mode = lm

            payload = (
                [] if p.kind in ("semi", "anti") else list(p.right.output_names())
            )

            if residual and p.kind in ("semi", "anti"):
                rid = f"__rowid_{ordinal(p)}"
                rowid = jnp.arange(lc.capacity, dtype=jnp.int64)
                lc2 = lc.with_columns([Field(rid, T.BIGINT, False)], [rowid], [None])
                key = f"join_{ordinal(p)}"
                cap = caps.get(key, pad_capacity(lc.capacity))
                expanded, total = hash_join_expand(
                    lc2, rc, tuple(probe_keys), tuple(build_keys), cap, INNER,
                    payload=list(p.right.output_names()), bit_widths=bit_widths,
                )
                checks[key] = total[None]
                matched = filter_chunk(expanded, and_all(residual))
                ids, _ = hash_aggregate(matched, ((rid, Col(rid)),), (), lc.capacity)
                out = hash_join_unique(
                    lc2, ids, (Col(rid),), (Col(rid),),
                    LEFT_SEMI if p.kind == "semi" else LEFT_ANTI, payload=[],
                )
                return out, out_mode

            if unique and p.kind in ("inner", "left", "semi", "anti"):
                if residual and p.kind != "inner":
                    raise PlanError(f"residual on {p.kind} join unsupported")
                out = hash_join_unique(
                    lc, rc, tuple(probe_keys), tuple(build_keys), kind,
                    payload=payload, bit_widths=bit_widths,
                )
                if residual:
                    out = filter_chunk(out, and_all(residual))
                return out, out_mode

            if residual and p.kind not in ("inner", "cross"):
                raise PlanError(f"residual on {p.kind} join unsupported")
            key = f"join_{ordinal(p)}"
            cap = caps.get(key, pad_capacity(lc.capacity))
            out, total = hash_join_expand(
                lc, rc, tuple(probe_keys), tuple(build_keys), cap, kind,
                payload=payload, bit_widths=bit_widths,
            )
            if p.kind not in ("semi", "anti"):
                checks[key] = total[None]
            if residual:
                out = filter_chunk(out, and_all(residual))
            return out, out_mode

        chunk, mode = emit(root_node)
        if mode != REPLICATED and (fragment is None or fragment.sink):
            # result delivery: the coordinator gather (sink fragments only —
            # interior fragments hand their sharded output to the consumer)
            note(None, 0, root_node, "gather", (), REPLICATED, "rows",
                 mode, chunk)
            chunk = all_gather(chunk)
        return chunk, checks

    return DistCompiled(
        step, scans, scan_mode_list, None, root_node.output_names(), n_shards,
        scopes=scope_table(scopes), compactions=compactions,
        exchanges=exchanges, segment_sums=segment_sums,
        dict_predicates=dict_predicates,
    )
