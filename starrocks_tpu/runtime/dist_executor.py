"""Distributed executor: run SQL plans as fragment programs over a mesh.

Reference behavior: the coordinator deploying fragments to N BEs and
collecting results (qe/DefaultCoordinator.java:599 deliverExecFragments ->
bRPC exec_plan_fragment -> ResultSink). TPU version: the plan splits at
exchange boundaries into a fragment IR (sql/fragments.py) and each fragment
compiles as its own jitted shard_map program with a DECLARED placement;
exchange edges lower to in-mesh collectives and fragment outputs feed
downstream fragments as device arrays without a host round-trip. On a
multi-process (global) mesh the same programs span hosts — each process
contributes its local devices and the collectives ride the DCN transport
when jaxlib provides one (gloo on CPU). `SET dist_fragments = false`
restores the pre-IR path: the WHOLE plan as one monolithic SPMD program
(the byte-identity A/B anchor — fragment execution preserves op order and
capacity keys exactly, so both paths produce identical device programs
modulo the fragment cuts).

Shares the Session's DeviceCache (so DML invalidation covers this path) and
the Executor's adaptive overflow-recompile loop; checks come back per-shard
and the host takes the max (profile counters are psum'd on device by the
sharded stages that emit them, so the max IS the cross-shard sum — and on a
multi-process mesh every host computes the same merged value, keeping the
psum-before-host-sum invariant).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from ..cache.keys import fragment_program_key
from ..column import Chunk
from ..parallel.mesh import make_mesh, shard_map
from ..sql.distributed import REPLICATED, compile_distributed, plan_scan_modes
from . import lifecycle
from .config import config
from .executor import Executor, program_name
from .metrics import EXCHANGE_BYTES, EXCHANGE_SLOTS, EXCHANGES
from .profile import RuntimeProfile


class DistExecutor(Executor):
    """Executes optimized logical plans over an n-device mesh."""

    def __init__(self, catalog, mesh=None, n_shards: int | None = None,
                 device_cache=None):
        super().__init__(catalog, device_cache)
        self.mesh = mesh or make_mesh(n_shards)
        self.axis = self.mesh.axis_names[0]
        self.n = self.mesh.shape[self.axis]
        # fragment IRs per (plan, scan-mode vector); see _fragment_ir
        self._frag_ir_memo: dict = {}

    def _verify_plan(self, plan, profile):
        """Adds the distribution pass on top of the structural passes: the
        plan must admit a legal partitioned lowering under the compiler's
        own placement rules (managed mode — the annotated fragment IR gets
        the stricter declared-mode pass in _fragment_ir once it exists)."""
        super()._verify_plan(plan, profile)
        from ..analysis import report, verify_level
        from ..analysis.plan_check import check_distribution

        if verify_level() == "off":
            return
        try:
            findings = check_distribution(plan, self.catalog)
        except Exception:  # noqa: BLE001  # lint: swallow-ok — verifier bug, not a query bug
            return
        report(findings, profile, where="distribution")

    def _run(self, plan, profile: RuntimeProfile | None = None) -> Chunk:
        if config.get("dist_fragments"):
            return self._run_fragments(plan, profile)
        return self._run_monolithic(plan, profile)

    def _run_monolithic(self, plan,
                        profile: RuntimeProfile | None = None) -> Chunk:
        profile = profile or RuntimeProfile("dist-query")

        # per-segment partial-aggregation cache (cache/partial.py): the
        # tier is host-orchestrated over manifest segments, so a cacheable
        # stored-table fragment takes the same path on every topology —
        # states cached by a single-chip run serve the distributed executor
        # and vice versa (the Session shares one DeviceCache/QueryCache
        # across both), and the merge is the engine's FINAL re-aggregation
        # rather than a mesh exchange. Non-matching plans (joins, in-memory
        # tables) fall through to the shard_map pipeline below.
        out = self._try_partial_cache(plan, profile)
        if out is not None:
            return out

        key = ("dist", self.n, plan)
        box: dict = {}

        def attempt(caps, p):
            def compile_cb():
                compiled = compile_distributed(
                    plan, self.catalog, caps, self.n, self.axis
                )
                box["facts"] = self._name_program(
                    compiled, key, program_name(plan, self._fb_fp()))
                scans_meta = tuple(zip(compiled.scans, compiled.scan_modes))
                inputs0 = self._place(scans_meta, p)
                in_specs = tuple(
                    jax.tree_util.tree_map(
                        lambda _, mm=m: P() if mm == REPLICATED else P(self.axis),
                        chunk,
                    )
                    for chunk, (_, m) in zip(inputs0, scans_meta)
                )
                raw = shard_map(
                    compiled.fn, mesh=self.mesh,
                    in_specs=(in_specs,),
                    out_specs=(P(), P(self.axis)),
                    check_vma=False,
                )
                # raw (the un-jitted shard_map) goes to the trace auditor:
                # its jaxpr exposes the shard_map body, where the psum-
                # shaped-counter check runs
                return jax.jit(raw), scans_meta, raw

            out, checks = self._cached_attempt(
                key, caps, p, compile_cb, lambda s: self._place(s, p))
            facts = self._ran(key, caps, box.pop("facts", None))
            return out, self._attempt_infos(p, caps, [facts], checks)

        def publish(vals):
            self.cache.bucket_last_set(self.cache.program_bucket(key), vals)

        out = self._adaptive(profile, attempt, publish)
        self._bind_operators(profile, self._dist_node_ord(plan))
        self._name_statement(profile, [key])
        return out

    # --- names, facts and counters of mesh programs ---------------------------

    def _fb_fp(self):
        return (self._fb_ctx or {}).get("fp")

    def _name_program(self, compiled, key, name: str) -> dict:
        """The XLA module is named after the statement (`q_<8 hex>`, a
        fragment's with `_f<fid>`), as on one chip: `compiled.fn` takes the
        name before shard_map and jit wrap it. The name and the scope table
        stay with the program's bucket, for cache hits, which never
        re-trace. Returns what the trace will fill in about the program."""
        compiled.fn.__name__ = compiled.fn.__qualname__ = name
        self.cache.bucket_meta_set(
            self.cache.program_bucket(key), "names", (name, compiled.scopes))
        return {"name": name, "compactions": compiled.compactions,
                "exchanges": compiled.exchanges,
                "segment_sums": compiled.segment_sums,
                "dict_predicates": compiled.dict_predicates}

    def _ran(self, key, caps, fresh: dict | None) -> dict:
        """After a mesh program ran, compiled just now (`fresh`) or cached:
        its facts, and its exchanges on the counters — from the static
        shapes of the program that ran, not the planner's estimates."""
        facts = self._program_facts(
            self.cache.program_bucket(key), caps, fresh)
        done = facts.get("exchanges", ())
        EXCHANGES.inc(len(done))
        EXCHANGE_SLOTS.inc(sum(e["slots"] for e in done))
        EXCHANGE_BYTES.inc(sum(e["bytes"] for e in done))
        return facts

    def _attempt_infos(self, p, caps, facts: list, checks: dict) -> list:
        """The attempt's checks merged on the host, and on its profile what
        its programs' facts say: `n_shards`, `programs` (module name -> its
        compactions, each with the `live` rows its check counted, and its
        exchanges), `compactions`, `segment_sums` and `dict_predicates` (all
        of them, as on one chip), and for every all_to_all `exchange_fill`,
        its fullest bucket (the overflow check's value, on the host anyway)
        over the bucket's capacity — skew and padding, read off a
        statement."""
        keyed = [(k, self._host_max(v)) for k, v in checks.items()]
        fullest = dict(keyed)
        p.set_info("n_shards", self.n)
        programs = {
            f["name"]: {"compactions": self._compactions_with_live(
                            f["compactions"], fullest),
                        "exchanges": list(f["exchanges"])}
            for f in facts if f}
        p.set_info("programs", programs)
        done = {k: c for holds in programs.values()
                for k, c in holds["compactions"].items()}
        if done:
            p.set_info("compactions", done)
        for info in ("segment_sums", "dict_predicates"):
            found = {k: c for f in facts for k, c in f.get(info, {}).items()}
            if found:
                p.set_info(info, found)
        fill = {e["check"]: round(fullest[e["check"]]
                                  / caps.values[e["check"]], 4)
                for f in facts for e in f.get("exchanges", ())
                if e["check"] in fullest}
        if fill:
            p.set_info("exchange_fill", fill)
        return keyed

    def _name_statement(self, profile, keys: list):
        """A device trace back to this statement: `program` lists its
        modules' names (jit_<name>; one a fragment, in fragment order),
        their operations sit under sr.<kind>.<n> scopes; `query_id` joins
        the fragment timers to information_schema.query_profiles."""
        names = [n for n in (
            self.cache.bucket_meta_get(self.cache.program_bucket(k), "names")
            for k in keys) if n]
        if names:
            profile.set_info("program", [name for name, _ in names])
            profile.set_info("scopes", names[0][1])
        ctx = lifecycle.current()
        if ctx is not None:
            profile.set_info("query_id", ctx.qid)

    @staticmethod
    def _dist_node_ord(plan) -> dict:
        """The distributed compiler's node-ordinal table, reconstructed
        host-side: compile_distributed assigns deterministic PRE-ORDER
        ordinals over walk_plan before lowering (sql/distributed.py), so
        the table needs no trace — attribution works identically on
        program-cache hits and across the monolithic/fragment A/B pair."""
        from ..sql.logical import walk_plan

        node_ord: dict = {}
        for nd in walk_plan(plan):
            node_ord.setdefault(nd, len(node_ord))
        return node_ord

    @staticmethod
    def _host_max(v) -> int:
        """Host max-merge of a per-shard check/counter output.

        On a single-process mesh every shard is addressable and a plain
        np max suffices. On a multi-process mesh the sharded output is not
        fully addressable: each process maxes ITS shards, then the partials
        all-gather across processes so every process adapts capacities from
        the same global values — divergent caps would compile divergent
        programs and deadlock the collectives. Counters stay exact because
        they are psum'd IN-PROGRAM over the full mesh axis first (the
        psum-before-host-sum convention); the host merge only picks the
        replicated result.
        """
        shards = getattr(v, "addressable_shards", None)
        if shards is not None and not v.is_fully_addressable:
            local = max(int(np.asarray(s.data).max()) for s in shards)
            from jax.experimental import multihost_utils

            merged = multihost_utils.process_allgather(
                np.asarray(local, np.int64))
            return int(np.asarray(merged).max())
        return int(np.asarray(v).max())

    def _place(self, scans_meta, profile=None):
        return tuple(
            self.cache.chunk_for(
                self.catalog.get_table(t), a, cols,
                placement=(self.mesh, self.axis, m), profile=profile,
            )
            for (t, a, cols), m in scans_meta
        )

    # --- fragment-IR execution path -------------------------------------------

    def _scan_in_specs(self, inputs0, scans_meta):
        return tuple(
            jax.tree_util.tree_map(
                lambda _, mm=m: P() if mm == REPLICATED else P(self.axis),
                chunk,
            )
            for chunk, (_, m) in zip(inputs0, scans_meta)
        )

    def _fragment_ir(self, plan, profile):
        """Build (and memoize) the fragment IR: trace the full plan once
        under jax.eval_shape with an ExchangeRecorder attached — the
        compiler notes every collective with the plan edge it implements —
        then split at the recorded edges (sql/fragments.py). The annotated
        plan goes through the DECLARED-mode distribution pass
        (managed_exchanges=False): plan_check verifies the declarations
        instead of re-simulating the compiler. Memoized per (plan,
        scan-mode vector) so a DML crossing the shard threshold re-derives
        the IR; scratch capacities are fine — exchange decisions depend on
        modes/dtypes/estimates, never on capacity values."""
        from ..sql.fragments import ExchangeRecorder, split
        from ..sql.logical import LScan, walk_plan
        from ..sql.physical import Caps

        scan_modes = plan_scan_modes(plan, self.catalog)
        mode_vec = tuple(
            str(scan_modes.get(id(nd), REPLICATED))
            for nd in walk_plan(plan) if isinstance(nd, LScan)
        )
        key = (plan, mode_vec)
        hit = self._frag_ir_memo.get(key)
        if hit is not None:
            return hit
        rec = ExchangeRecorder()
        compiled = compile_distributed(
            plan, self.catalog, Caps({}), self.n, self.axis, scan_modes,
            recorder=rec,
        )
        scans_meta = tuple(zip(compiled.scans, compiled.scan_modes))
        inputs0 = self._place(scans_meta, profile)
        raw = shard_map(
            compiled.fn, mesh=self.mesh,
            in_specs=(self._scan_in_specs(inputs0, scans_meta),),
            out_specs=(P(), P(self.axis)),
            check_vma=False,
        )
        jax.eval_shape(raw, inputs0)
        ir = split(plan, rec.events)
        self._verify_fragment_ir(ir, profile)
        if len(self._frag_ir_memo) > 256:
            self._frag_ir_memo.clear()
        self._frag_ir_memo[key] = (ir, scans_meta)
        return ir, scans_meta

    def _verify_fragment_ir(self, ir, profile):
        """Declared-distribution verification of the annotated IR. The
        exchanges are explicit LExchange nodes now, so the pass checks the
        DECLARATIONS (placement tokens, exchange keys against join/group/
        partition keys, replicated-at-root) — a compiler bug that records a
        wrong exchange set surfaces here instead of being mirrored by a
        simulation of the same code."""
        from ..analysis import report, verify_level
        from ..analysis.plan_check import check_distribution

        if verify_level() == "off":
            return
        try:
            findings = check_distribution(
                ir.annotated, self.catalog, managed_exchanges=False)
        except Exception:  # noqa: BLE001  # lint: swallow-ok — verifier bug, not a query bug
            return
        report(findings, profile, where="fragment-ir")

    def _run_fragments(self, plan,
                       profile: RuntimeProfile | None = None) -> Chunk:
        profile = profile or RuntimeProfile("dist-query")
        out = self._try_partial_cache(plan, profile)
        if out is not None:
            return out
        ir, scans_meta = self._fragment_ir(plan, profile)
        # the memo hits on plan EQUALITY: fragment roots/boundaries are
        # nodes of the plan the IR was DERIVED from, and the compiler's
        # scan table is id()-keyed — compile against that same object
        # (an equal-but-distinct plan, e.g. one that crossed the cluster
        # wire or came from a different statement text, would KeyError)
        plan = ir.plan
        st = ir.stats()
        profile.set_info("fragments", st["fragments"])
        profile.set_info("exchanges", st["exchanges"])
        profile.add_counter("exchange_rows", st["exchange_rows"])
        profile.add_counter("exchange_bytes", st["exchange_bytes"])
        profile.set_info("fragment_topology", st["per_fragment"])

        cluster = getattr(self.catalog, "cluster_runtime", None)
        if cluster is not None and self._cluster_eligible(ir, scans_meta):
            return self._run_cluster(cluster, plan, ir, scans_meta, profile)

        name = program_name(plan, self._fb_fp())

        def attempt(caps, p):
            with p.timer("scan_to_device"):
                inputs = self._place(scans_meta, p)
            outputs: dict = {}
            merged: dict = {}
            facts: list = []
            for frag in ir.fragments:
                bnd = tuple(outputs[d] for d in frag.deps)
                out_f, checks, ran = self._fragment_attempt(
                    plan, frag, caps, p, inputs, bnd, scans_meta,
                    f"{name}_f{frag.fid}")
                outputs[frag.fid] = out_f
                # capacity keys carry GLOBAL pre-order ordinals: a node's
                # ops live in one fragment (re-emitted CSE twins compute
                # identical values), so merging by update is exact
                merged.update(checks)
                facts.append(ran)
            final = outputs[ir.fragments[-1].fid]
            return final, self._attempt_infos(p, caps, facts, merged)

        def publish(vals):
            # the adoption seed: fragment 0's bucket is the first one
            # consulted on the next run (caps still empty there)
            self.cache.bucket_last_set(
                self.cache.program_bucket(
                    fragment_program_key(self.n, plan, ir.fragments[0])),
                vals)

        out = self._adaptive(profile, attempt, publish)
        self._bind_operators(profile, self._dist_node_ord(plan))
        self._name_statement(profile, [
            fragment_program_key(self.n, plan, f) for f in ir.fragments])
        return out

    @staticmethod
    def _cluster_eligible(ir, scans_meta) -> bool:
        """Route to the cluster runtime only when the exchange plane can
        pay for itself AND every scan is a shippable stored/mem table:
        information_schema and hidden tables are process-local state — a
        worker's copy would answer about the WRONG process."""
        if len(ir.fragments) < int(
                config.get("cluster_route_min_fragments")):
            return False
        return all(
            not t.startswith(("information_schema.", "__"))
            for (t, _a, _c), _m in scans_meta
        )

    def _run_cluster(self, cluster, plan, ir, scans_meta, profile) -> Chunk:
        """Coordinator-side cluster scheduling: fragments go out in topo
        order, one request per fragment; boundary outputs come back as
        host pytrees and are cached HERE, so a worker lost mid-query
        costs one fragment re-placement, never a query restart
        (cluster_exec.ClusterRuntime owns retry + liveness). Runs inside
        the session's normal query scope — kill/deadline checkpoints and
        the admission/accountant unwind hold unchanged under loss."""
        import pickle

        from .cluster_exec import plan_fingerprint

        blob = pickle.dumps(plan, protocol=4)
        fp = plan_fingerprint(blob)
        tables = tuple(t for (t, _a, _c), _m in scans_meta)
        profile.set_info("cluster_workers", cluster.stats()["alive"])
        outputs: dict = {}
        for frag in ir.fragments:
            lifecycle.checkpoint("cluster::fragment")
            bnd = tuple(outputs[d] for d in frag.deps)
            with profile.timer(f"fragment_{frag.fid}_cluster"):
                out = cluster.exec_fragment(
                    blob, fp, frag.fid, bnd, tables, profile)
            lifecycle.account(out, "cluster::fragment")
            outputs[frag.fid] = out
        self._bind_operators(profile, self._dist_node_ord(plan))
        return outputs[ir.fragments[-1].fid]

    def _fragment_attempt(self, plan, frag, caps, p, inputs, bnd,
                          scans_meta, name: str):
        """One fragment through the shared program-cache protocol
        (Executor._cached_attempt), as step(inputs, bnd) under the module
        name `name`. The capacity dict is SHARED across the query's
        fragments — keys carry global plan ordinals — so a fragment's
        program key is the full caps snapshot at its compile time. A
        snapshot taken mid-first-run lacks downstream fragments' keys,
        which costs one extra compile on the next run (the key then
        includes everything) and stabilizes from the run after — the same
        convergence the tightening pass already imposes on the monolithic
        path. Returns (chunk, checks, the program's facts)."""
        key = fragment_program_key(self.n, plan, frag)
        box: dict = {}

        def compile_cb():
            compiled = compile_distributed(
                plan, self.catalog, caps, self.n, self.axis,
                dict(self._scan_mode_dict(scans_meta, plan)), fragment=frag,
            )
            box["facts"] = self._name_program(compiled, key, name)
            bnd_specs = tuple(
                jax.tree_util.tree_map(lambda _: P(self.axis), ch)
                for ch in bnd
            )
            out_spec = P() if frag.out_mode == REPLICATED else P(self.axis)
            raw = shard_map(
                compiled.fn, mesh=self.mesh,
                in_specs=(self._scan_in_specs(inputs, scans_meta), bnd_specs),
                out_specs=(out_spec, P(self.axis)),
                check_vma=False,
            )
            return jax.jit(raw), scans_meta, raw

        # per-fragment compile vs execute split: the trace happens lazily
        # inside the first call, so the compile timer covers lowering +
        # trace + that call, the execute timer a cached program's call
        out, checks = self._cached_attempt(
            key, caps, p, compile_cb, None, placed=inputs, extra_args=(bnd,),
            phase=f"fragment_{frag.fid}")
        return out, checks, self._ran(key, caps, box.pop("facts", None))

    @staticmethod
    def _scan_mode_dict(scans_meta, plan):
        """Rebuild the id-keyed scan-mode dict the compiler expects from
        the (table, alias, columns) -> mode pairs pinned in scans_meta, so
        a cached IR replays with the modes it was derived under (not modes
        recomputed from a catalog that DML may have shifted since)."""
        from ..sql.logical import LScan, walk_plan

        by_key = {s: m for s, m in scans_meta}
        return {
            id(nd): by_key[(nd.table, nd.alias, nd.columns)]
            for nd in walk_plan(plan) if isinstance(nd, LScan)
            if (nd.table, nd.alias, nd.columns) in by_key
        }
