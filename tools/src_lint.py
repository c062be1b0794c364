#!/usr/bin/env python
"""AST source lint for JAX pitfalls in starrocks_tpu/.

Four rules, all for bug classes that pass every unit test and then burn
on real hardware (or real traffic):

R1 shard-map-shim: `shard_map` must be imported from parallel/mesh.py (the
   single import point, which turns the replication/VMA check off the way
   every engine program needs), never from jax directly — so a jax move or
   kwarg rename is one edit.

R2 traced-host-op: inside TRACED scopes — functions handed to jax.jit /
   shard_map, and the program closures built by compile_plan /
   compile_distributed (`run` / `step`) — calling `.item()` or
   `np.asarray`/`np.array` on a traced value either crashes at trace time
   (ConcretizationTypeError) or silently freezes a trace-time constant into
   the program. Host callbacks registered via pure_callback/io_callback/
   debug_callback are exempt (numpy there is the point), as is any line
   tagged `# lint: host-ok`.

R3 cache-key-knob: inside the query cache's key builders
   (starrocks_tpu/cache/keys.py), every LITERAL `config.get("name")` must
   name a knob declared `trace=True` or `cache_key=True` at its
   `config.define` site (statically parsed from runtime/config.py — no
   import needed). Undeclared reads punch a hole in the result-key
   completeness proof: analysis/key_check.py audits the DYNAMIC read-set,
   this rule pins the STATIC one, and the two meet at the declaration.
   Non-literal reads (`config.get(k) for k in OPT_KEY_KNOBS`) are the
   shared opt-key channel and stay legal.

R4 swallowed-exception: in starrocks_tpu/runtime/, an `except Exception`
   (or bare `except`) handler must re-raise, convert to a typed query
   error (any `raise` in the handler body), or carry `# lint: swallow-ok`
   on its `except` line. A silently swallowed exception in the runtime is
   how admission slots leak, journals wedge half-written, and killed
   queries report success — the failure classes tests/test_chaos.py
   injects. Deliberate swallows (liveness loops, best-effort listeners)
   stay legal via the tag, which doubles as documentation.

R5 serve-query-scope: the serving tier's executor-pool worker body
   (runtime/serving.py `_run_statement`) must execute its statement via
   `session.sql(...)` INSIDE a `with ... query_scope(...)` block, and
   nothing in serving.py may call the session's internal execution
   surfaces (`_sql_inner` / `_query_planned` / `_query_admitted` /
   `execute_logical`) directly. A statement that runs outside a
   query_scope is invisible to SHOW PROCESSLIST, unkillable, deadline-
   free, and unaccounted — the exact bug class thread fan-out invites.

R6 feedback-key-knob: in the plan-feedback consult path
   (starrocks_tpu/runtime/feedback.py), every LITERAL `config.get("name")`
   must name a knob on SOME cache-key channel: declared trace=True or
   cache_key=True at its config.define site, or listed in OPT_KEY_KNOBS /
   HOST_LOOP_KNOBS (analysis/key_check.py). Feedback entries are keyed by
   a fingerprint over exactly those channels — a consult that also reads
   an un-channeled knob could hand two different observation sets to two
   executions with identical fingerprints, silently splitting the learned
   state (analysis/key_check.check_feedback_reads audits the DYNAMIC
   read-set; this rule pins the STATIC one).

R7 metric-name-prefix: every LITERAL metric name handed to
   `metrics.counter/gauge/histogram(...)` must start with `sr_tpu_`. The
   /metrics scrape is consumed by Prometheus relabel rules and dashboards
   keyed on that prefix; one unprefixed series silently drops out of every
   alert. Enforced at the declaration site so the tier-1 live-scrape check
   (tools/check_metrics_endpoint.py) can assert the same invariant on the
   wire and the two meet at the registry.

R8 point-query-scope: the short-circuit point lane's execution entry
   (runtime/point.py `try_execute`) may be called from exactly ONE place —
   `Session._sql_inner` (runtime/session.py), which always runs inside
   `lifecycle.query_scope` (the R5 contract applied to the lane). Serving
   code may consult the PURE text probe `point.peek_select` for its gate
   claim but must never call the lane's execution internals; a second
   entry point would execute PK lookups outside the registered/killable/
   accounted plane. `try_execute` itself must hit a `lifecycle.checkpoint`
   before the index probe so an in-flight KILL lands.

R9 event-taxonomy: system events are journaled ONLY through the
   sanctioned API `events.emit("<name>", ...)` with a LITERAL name in
   the closed TAXONOMY statically parsed from runtime/events.py (no
   import — same discipline as R3/R6). Computed names, off-taxonomy
   literals, and direct `EVENTS.emit(...)` calls outside events.py all
   fail: the taxonomy is the contract dashboards and the /api/events
   schema check key on, and an ad-hoc event string silently drops out
   of every per-type counter.

The lint also counts `fail_point()` call sites across the package and
fails below the chaos-suite floor (MIN_FAILPOINT_SITES): fault-injection
coverage is an invariant here, not a nice-to-have.

Exit 1 on any finding; each names file:line, the rule, and the offending op.
"""

from __future__ import annotations

import ast
import importlib.util
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "starrocks_tpu")
SHIM = os.path.join("starrocks_tpu", "parallel", "mesh.py")


def _astwalk():
    """The shared AST walk (analysis/astwalk.py): every static gate —
    src_lint, concur_lint — reads the SAME parsed tree per module instead
    of re-parsing the package per checker. Loaded by file path: importing
    the starrocks_tpu package would pull jax, and this lint must run on a
    bare checkout."""
    mod = sys.modules.get("sr_astwalk")
    if mod is not None:
        return mod
    spec = importlib.util.spec_from_file_location(
        "sr_astwalk", os.path.join(PKG, "analysis", "astwalk.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["sr_astwalk"] = mod
    spec.loader.exec_module(mod)
    return mod

CALLBACK_FNS = {"pure_callback", "io_callback", "debug_callback"}
TRACE_BUILDERS = {"compile_plan": {"run"}, "compile_distributed": {"step"}}


def _call_name(node: ast.Call) -> str:
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


def _is_np(node) -> bool:
    return isinstance(node, ast.Name) and node.id in ("np", "numpy")


class Linter(ast.NodeVisitor):
    def __init__(self, path: str, rel: str, src: str):
        self.path = path
        self.rel = rel
        self.lines = src.splitlines()
        self.findings: list = []
        self._traced_depth = 0
        self._func_stack: list = []
        # names of local functions passed to jit/shard_map somewhere in
        # this module: defs with those names are traced roots
        self.traced_names: set = set()
        # (lineno of defs that are callback host-fns) — exempt subtrees
        self.callback_args: set = set()

    def add(self, node, rule, msg):
        line = self.lines[node.lineno - 1] if node.lineno <= len(
            self.lines) else ""
        if "lint: host-ok" in line:
            return
        self.findings.append(f"{self.rel}:{node.lineno}: [{rule}] {msg}")

    # --- pass 1: collect traced / callback names -----------------------------
    def collect(self, tree):
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                name = _call_name(node)
                if name in ("jit", "shard_map"):
                    for a in node.args:
                        if isinstance(a, ast.Name):
                            self.traced_names.add(a.id)
                if name in CALLBACK_FNS and node.args:
                    a = node.args[0]
                    if isinstance(a, ast.Name):
                        self.callback_args.add(a.id)

    # --- pass 2: walk with traced-scope tracking -----------------------------
    def visit_Import(self, node):
        self.generic_visit(node)

    def visit_ImportFrom(self, node):
        mod = node.module or ""
        names = {a.name for a in node.names}
        if self.rel != SHIM and (
                ("shard_map" in names and mod.startswith("jax"))
                or mod == "jax.experimental.shard_map"):
            self.add(node, "shard-map-shim",
                     f"import shard_map from parallel/mesh.py, not "
                     f"{mod!r} (version shim bypassed)")
        self.generic_visit(node)

    def visit_Attribute(self, node):
        # jax.experimental.shard_map.* attribute access
        if (node.attr == "shard_map" and isinstance(node.value, ast.Attribute)
                and node.value.attr == "experimental"
                and self.rel != SHIM):
            self.add(node, "shard-map-shim",
                     "use parallel/mesh.py's shard_map shim")
        self.generic_visit(node)

    def _enter_func(self, node):
        traced = False
        name = getattr(node, "name", "<lambda>")
        if name in self.traced_names:
            traced = True
        parent = self._func_stack[-1] if self._func_stack else None
        if parent is not None and name in TRACE_BUILDERS.get(parent, ()):
            traced = True
        if self._traced_depth and name in self.callback_args:
            traced = False  # host callback body nested in a traced scope
            self._func_stack.append(name)
            self._visit_body(node, bump=0, host_exempt=True)
            self._func_stack.pop()
            return
        self._func_stack.append(name)
        self._visit_body(node, bump=1 if (traced or self._traced_depth) else 0)
        self._func_stack.pop()

    def _visit_body(self, node, bump: int, host_exempt: bool = False):
        if host_exempt:
            # walk without traced context (nested defs restart clean)
            saved = self._traced_depth
            self._traced_depth = 0
            for child in ast.iter_child_nodes(node):
                self.visit(child)
            self._traced_depth = saved
            return
        self._traced_depth += bump
        for child in ast.iter_child_nodes(node):
            self.visit(child)
        self._traced_depth -= bump

    def visit_FunctionDef(self, node):
        self._enter_func(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node):
        self._visit_body(node, bump=0)

    def visit_Call(self, node):
        if self._traced_depth:
            name = _call_name(node)
            if name == "item" and isinstance(node.func, ast.Attribute):
                self.add(node, "traced-host-op",
                         ".item() inside a traced function pulls the value "
                         "to host (trace-time concretization)")
            if name in ("asarray", "array") and isinstance(
                    node.func, ast.Attribute) and _is_np(node.func.value):
                self.add(node, "traced-host-op",
                         f"np.{name}() inside a traced function freezes a "
                         f"trace-time constant (use jnp, or tag the line "
                         f"`# lint: host-ok` if the operand is static)")
        self.generic_visit(node)


RUNTIME_PREFIX = os.path.join("starrocks_tpu", "runtime") + os.sep
MIN_FAILPOINT_SITES = 51  # ratchet: includes the ingest plane's 4 sites
#                           (ingest::stage/commit/label_journal/poll)


def _is_exception_catch(handler: ast.ExceptHandler) -> bool:
    """True for `except Exception` / bare `except` (incl. tuples holding
    Exception). Narrow typed catches are R4-exempt: they name what they
    swallow."""
    t = handler.type
    if t is None:
        return True
    names = []
    if isinstance(t, ast.Tuple):
        names = [e.id for e in t.elts if isinstance(e, ast.Name)]
    elif isinstance(t, ast.Name):
        names = [t.id]
    return "Exception" in names or "BaseException" in names


def lint_runtime_swallow(path: str, rel: str, src: str, tree) -> list:
    """R4: see module docstring."""
    if not rel.startswith(RUNTIME_PREFIX):
        return []
    lines = src.splitlines()
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if not _is_exception_catch(node):
            continue
        line = lines[node.lineno - 1] if node.lineno <= len(lines) else ""
        if "lint: swallow-ok" in line:
            continue
        if any(isinstance(n, ast.Raise) for b in node.body
               for n in ast.walk(b)):
            continue  # re-raises or converts to a typed error
        findings.append(
            f"{rel}:{node.lineno}: [runtime-swallow] `except Exception` in "
            f"runtime/ must re-raise, convert to a typed query error, or "
            f"carry `# lint: swallow-ok` on the except line")
    return findings


def count_failpoints(sources) -> int:
    """Static count of fail_point(...) call sites across the package (the
    chaos-coverage floor reported next to the findings)."""
    n = 0
    for ms in sources:
        for node in ast.walk(ms.tree):
            if isinstance(node, ast.Call) \
                    and _call_name(node) == "fail_point":
                n += 1
    return n


CACHE_KEY_MODULE = os.path.join("starrocks_tpu", "cache", "keys.py")
CONFIG_MODULE = os.path.join(PKG, "runtime", "config.py")


def _declared_key_knobs() -> dict:
    """{knob name: (trace, cache_key)} parsed from the config.define calls
    in runtime/config.py — purely static, so the lint needs no package
    import (and can't be fooled by runtime monkey-patching)."""
    with open(CONFIG_MODULE) as f:
        tree = ast.parse(f.read())
    out = {}
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call) and _call_name(node) == "define"
                and node.args and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            flags = {
                kw.arg: bool(kw.value.value)
                for kw in node.keywords
                if kw.arg in ("trace", "cache_key")
                and isinstance(kw.value, ast.Constant)
            }
            out[node.args[0].value] = (
                flags.get("trace", False), flags.get("cache_key", False))
    return out


def lint_cache_keys() -> list:
    """R3: literal config.get reads inside cache-key construction must be
    declared trace=True or cache_key=True (see module docstring)."""
    path = os.path.join(REPO, CACHE_KEY_MODULE)
    if not os.path.exists(path):
        return []
    declared = _declared_key_knobs()
    with open(path) as f:
        src = f.read()
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [f"{CACHE_KEY_MODULE}:{e.lineno}: [parse] {e.msg}"]
    lines = src.splitlines()
    findings = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and isinstance(
                node.func, ast.Attribute) and node.func.attr == "get"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "config"
                and node.args and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            continue
        line = lines[node.lineno - 1] if node.lineno <= len(lines) else ""
        if "lint: host-ok" in line:
            continue
        name = node.args[0].value
        trace, cache_key = declared.get(name, (False, False))
        if not (trace or cache_key):
            findings.append(
                f"{CACHE_KEY_MODULE}:{node.lineno}: [cache-key-knob] "
                f"config.get({name!r}) inside cache-key construction: "
                f"declare the knob trace=True or cache_key=True at its "
                f"config.define site, or the result key cannot be proven "
                f"complete")
    return findings


FEEDBACK_MODULE = os.path.join("starrocks_tpu", "runtime", "feedback.py")
KEY_CHECK_MODULE = os.path.join(PKG, "analysis", "key_check.py")


def _keyed_knob_channels() -> set:
    """Every knob name on SOME cache-key channel: declared trace=True or
    cache_key=True in runtime/config.py, plus the members of OPT_KEY_KNOBS
    and HOST_LOOP_KNOBS in analysis/key_check.py — all statically parsed,
    same no-import discipline as R3."""
    names = {k for k, (t, c) in _declared_key_knobs().items() if t or c}
    with open(KEY_CHECK_MODULE) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        for tgt in node.targets:
            if not (isinstance(tgt, ast.Name)
                    and tgt.id in ("OPT_KEY_KNOBS", "HOST_LOOP_KNOBS")):
                continue
            v = node.value
            if isinstance(v, (ast.Tuple, ast.List)):
                names |= {e.value for e in v.elts
                          if isinstance(e, ast.Constant)
                          and isinstance(e.value, str)}
            elif isinstance(v, ast.Dict):
                names |= {k.value for k in v.keys
                          if isinstance(k, ast.Constant)
                          and isinstance(k.value, str)}
    return names


def lint_feedback_keys(src: str | None = None,
                       rel: str = FEEDBACK_MODULE) -> list:
    """R6: see module docstring. `src` is injectable so the golden
    bad-fixture test (tests/test_plan_feedback.py) can prove the rule
    rejects what it exists to reject."""
    if src is None:
        path = os.path.join(REPO, rel)
        if not os.path.exists(path):
            return [f"{rel}:1: [feedback-key-knob] plan-feedback module "
                    f"missing (the consult path is a keyed surface)"]
        with open(path) as f:
            src = f.read()
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [f"{rel}:{e.lineno}: [parse] {e.msg}"]
    channels = _keyed_knob_channels()
    lines = src.splitlines()
    findings = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and isinstance(
                node.func, ast.Attribute) and node.func.attr == "get"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "config"
                and node.args and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            continue
        line = lines[node.lineno - 1] if node.lineno <= len(lines) else ""
        if "lint: host-ok" in line:
            continue
        name = node.args[0].value
        if name not in channels:
            findings.append(
                f"{rel}:{node.lineno}: [feedback-key-knob] "
                f"config.get({name!r}) in the feedback consult path is on "
                f"no cache-key channel (trace/cache_key declaration, "
                f"OPT_KEY_KNOBS, or HOST_LOOP_KNOBS): identical plan "
                f"fingerprints could consult different observations")
    return findings


SERVING_MODULE = os.path.join("starrocks_tpu", "runtime", "serving.py")
_SESSION_INTERNALS = {"_sql_inner", "_query_planned", "_query_admitted",
                      "execute_logical"}


METRIC_PREFIX = "sr_tpu_"
_METRIC_FACTORIES = ("counter", "gauge", "histogram")


def lint_metric_names(sources) -> list:
    """R7: literal metric names at `metrics.counter/gauge/histogram(...)`
    declaration sites must carry the sr_tpu_ exporter prefix."""
    findings = []
    for ms in sources:
        for node in ast.walk(ms.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _METRIC_FACTORIES
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "metrics"):
                continue
            if not (node.args and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue  # computed names are registry-internal helpers
            name = node.args[0].value
            if not name.startswith(METRIC_PREFIX):
                findings.append(
                    f"{ms.rel}:{node.lineno}: [metric-name-prefix] "
                    f"metrics.{node.func.attr}({name!r}) — exported series "
                    f"must start with {METRIC_PREFIX!r}")
    return findings


def _declared_event_taxonomy() -> frozenset:
    """Statically parse the closed event taxonomy from the
    `TAXONOMY = frozenset((...))` literal in runtime/events.py — no
    import, same discipline as _declared_key_knobs."""
    path = os.path.join(REPO, "starrocks_tpu", "runtime", "events.py")
    try:
        with open(path) as f:
            tree = ast.parse(f.read())
    except (OSError, SyntaxError):
        return frozenset()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Assign) and node.targets
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "TAXONOMY"):
            continue
        names = set()
        for c in ast.walk(node.value):
            if isinstance(c, ast.Constant) and isinstance(c.value, str):
                names.add(c.value)
        return frozenset(names)
    return frozenset()


def lint_event_names(sources) -> list:
    """R9: see module docstring."""
    taxonomy = _declared_event_taxonomy()
    findings = []
    for ms in sources:
        in_events_module = ms.rel.endswith("runtime/events.py")
        for node in ast.walk(ms.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "emit"
                    and isinstance(node.func.value, ast.Name)):
                continue
            owner = node.func.value.id
            if owner == "EVENTS" and not in_events_module:
                findings.append(
                    f"{ms.rel}:{node.lineno}: [event-taxonomy] direct "
                    f"EVENTS.emit(...) — journal through the sanctioned "
                    f"events.emit(...) API")
                continue
            if owner != "events":
                continue
            if not (node.args and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                findings.append(
                    f"{ms.rel}:{node.lineno}: [event-taxonomy] "
                    f"events.emit(...) with a computed name — event types "
                    f"are a closed taxonomy (runtime/events.py)")
                continue
            name = node.args[0].value
            if name not in taxonomy:
                findings.append(
                    f"{ms.rel}:{node.lineno}: [event-taxonomy] "
                    f"events.emit({name!r}) — not in the declared "
                    f"taxonomy (runtime/events.py TAXONOMY)")
    return findings


def lint_serving_scope(sources) -> list:
    """R5: see module docstring."""
    ms = next((m for m in sources if m.rel == SERVING_MODULE), None)
    if ms is None:
        return [f"{SERVING_MODULE}:1: [serve-query-scope] serving tier "
                f"module missing (the executor pool is a tier-1 surface)"]
    findings = []
    run_fn = None
    for node in ast.walk(ms.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name == "_run_statement":
            run_fn = node
        if isinstance(node, ast.Call) \
                and _call_name(node) in _SESSION_INTERNALS:
            findings.append(
                f"{ms.rel}:{node.lineno}: [serve-query-scope] serving "
                f"code must execute statements via session.sql inside a "
                f"query_scope, never {_call_name(node)}() directly")
    if run_fn is None:
        findings.append(
            f"{ms.rel}:1: [serve-query-scope] missing `_run_statement` "
            f"worker body (the pool's single statement entry point)")
        return findings
    scoped_ok = False
    for node in ast.walk(run_fn):
        if not isinstance(node, (ast.With, ast.AsyncWith)):
            continue
        if not any(isinstance(i.context_expr, ast.Call)
                   and _call_name(i.context_expr) == "query_scope"
                   for i in node.items):
            continue
        inner = {_call_name(c) for b in node.body for c in ast.walk(b)
                 if isinstance(c, ast.Call)}
        if "sql" in inner:
            scoped_ok = True
    if not scoped_ok:
        findings.append(
            f"{ms.rel}:{run_fn.lineno}: [serve-query-scope] "
            f"_run_statement must call session.sql(...) INSIDE `with "
            f"query_scope(...)` — unregistered statement execution is "
            f"unkillable, deadline-free, and unaccounted")
    return findings


POINT_MODULE = os.path.join("starrocks_tpu", "runtime", "point.py")
SESSION_MODULE = os.path.join("starrocks_tpu", "runtime", "session.py")
_POINT_INTERNALS = {"try_execute", "_run_select", "_run_update",
                    "_run_delete", "_resolve"}


def lint_point_scope(sources) -> list:
    """R8: see module docstring."""
    pm = next((m for m in sources if m.rel == POINT_MODULE), None)
    if pm is None:
        return [f"{POINT_MODULE}:1: [point-query-scope] point-lane module "
                f"missing (the short-circuit read path is a tier-1 "
                f"surface)"]
    findings = []
    # the lane's entry must checkpoint before the probe: a KILL delivered
    # mid-lookup needs a stage boundary to land on
    entry = next((n for n in ast.walk(pm.tree)
                  if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                  and n.name == "try_execute"), None)
    if entry is None:
        findings.append(
            f"{pm.rel}:1: [point-query-scope] missing `try_execute` (the "
            f"lane's single execution entry point)")
    elif not any(isinstance(c, ast.Call) and _call_name(c) == "checkpoint"
                 for c in ast.walk(entry)):
        findings.append(
            f"{pm.rel}:{entry.lineno}: [point-query-scope] try_execute "
            f"must call lifecycle.checkpoint(...) before the index probe "
            f"— an unkillable point lane breaks the KILL contract")
    # callers: point-lane execution internals are reachable from exactly
    # one site, Session._sql_inner (itself pinned inside query_scope)
    for ms in sources:
        if ms.rel == POINT_MODULE:
            continue
        sql_inner = next(
            (n for n in ast.walk(ms.tree)
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
             and n.name == "_sql_inner"), None) \
            if ms.rel == SESSION_MODULE else None
        allowed = set()
        if sql_inner is not None:
            allowed = {id(c) for c in ast.walk(sql_inner)
                       if isinstance(c, ast.Call)}
        for node in ast.walk(ms.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _POINT_INTERNALS
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "point"):
                continue
            if id(node) in allowed:
                continue
            findings.append(
                f"{ms.rel}:{node.lineno}: [point-query-scope] "
                f"point.{node.func.attr}() outside Session._sql_inner — "
                f"the short-circuit lane must enter through the "
                f"query_scope'd session path (peek_select is the only "
                f"serving-side probe)")
    return findings


def lint_module(ms) -> list:
    linter = Linter(ms.path, ms.rel, ms.src)
    linter.collect(ms.tree)
    for node in ms.tree.body:
        linter.visit(node)
    return linter.findings + lint_runtime_swallow(
        ms.path, ms.rel, ms.src, ms.tree)


def main():
    try:
        sources = _astwalk().package_sources(REPO)
    except SyntaxError as e:
        print(f"{e.filename}:{e.lineno}: [parse] {e.msg}")
        print("src_lint: 1 finding(s); failpoint_sites=?")
        return 1
    findings = []
    for ms in sources:
        findings += lint_module(ms)
    findings += lint_cache_keys()
    findings += lint_feedback_keys()
    findings += lint_serving_scope(sources)
    findings += lint_metric_names(sources)
    findings += lint_point_scope(sources)
    findings += lint_event_names(sources)
    n_fp = count_failpoints(sources)
    if n_fp < MIN_FAILPOINT_SITES:
        findings.append(
            f"starrocks_tpu/: [failpoint-floor] only {n_fp} fail_point() "
            f"call sites; the chaos-suite floor is {MIN_FAILPOINT_SITES}")
    for f in findings:
        print(f)
    print(f"src_lint: {len(findings)} finding(s); failpoint_sites={n_fp}")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
