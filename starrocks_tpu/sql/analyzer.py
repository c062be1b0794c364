"""Semantic analysis: AST -> logical plan.

Reference behavior: fe sql/analyzer/Analyzer.java:192 + the relation
transformer (sql/optimizer/transformer/RelationTransformer.java) — scope-based
name resolution, aggregate extraction, subquery marking. Output columns are
qualified "alias.column" so self-joins (TPC-H Q21's three lineitem instances)
stay unambiguous.

Subqueries (ast.Subquery/Exists/InSubquery) survive analysis as expression
markers holding *analyzed* logical plans + correlation info; the optimizer
rewrites them into joins or the executor evaluates them (uncorrelated scalar).
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Optional

from ..exprs.ir import (
    AggExpr, Call, Case, Cast, Col, Expr, InList, Lit, WindowExpr,
    Lambda as IrLambda,
)
from . import ast
from .logical import (
    LAggregate, LFilter, LJoin, LLimit, LProject, LScan, LSort, LUnion,
    LUnnest, LWindow, LogicalPlan,
)


class AnalyzerError(ValueError):
    pass


# --- analyzed subquery markers (carried inside expressions) ------------------


@dataclasses.dataclass(frozen=True)
class ScalarSubquery(Expr):
    plan: LogicalPlan
    correlated: tuple  # tuple[(outer_col_name, inner_col_name)] equi-pairs

    def __repr__(self):
        return f"ScalarSubquery(corr={self.correlated})"


@dataclasses.dataclass(frozen=True)
class SemiJoinMark(Expr):
    """EXISTS / IN-subquery lowered to a (anti)semi-join marker."""

    plan: LogicalPlan
    correlated: tuple
    probe_expr: Optional[Expr]  # for IN: outer expr to match inner_col
    inner_col: Optional[str]
    negated: bool = False

    def __repr__(self):
        k = "anti" if self.negated else "semi"
        return f"SemiJoinMark[{k}]"


class Scope:
    """Visible columns: list of (alias, column_base_name) -> qualified name."""

    def __init__(self, entries, parent: Optional["Scope"] = None):
        # entries: list[(alias, tuple[base_names])]
        self.entries = entries
        self.parent = parent

    def resolve(self, table: Optional[str], name: str):
        """Returns (qualified_name, depth) — depth>0 means outer (correlated)."""
        hits = self._hits(table, name, lambda a, b: a == b)
        if not hits:
            # names resolve without regard to case, as MySQL's and
            # StarRocks' do (a DDL in capitals, statements in lower case);
            # the spelling as written wins where both exist
            hits = self._hits(table, name,
                              lambda a, b: a.lower() == b.lower())
        if len(hits) > 1:
            raise AnalyzerError(f"ambiguous column {name!r}: {hits}")
        if hits:
            return hits[0], 0
        if self.parent is not None:
            q, d = self.parent.resolve(table, name)
            return q, d + 1
        raise AnalyzerError(
            f"unknown column {(table + '.') if table else ''}{name}"
        )

    def _hits(self, table: Optional[str], name: str, same) -> list:
        return [f"{alias}.{c}" for alias, cols in self.entries
                if table is None or same(alias, table)
                for c in cols if same(c, name)]

    def resolve_or_none(self, table: Optional[str], name: str):
        try:
            return self.resolve(table, name)
        except AnalyzerError:
            return None

    def all_names(self):
        return [f"{a}.{c}" for a, cols in self.entries for c in cols]


class Analyzer:
    def __init__(self, catalog):
        self.catalog = catalog
        self._ids = itertools.count()
        self._view_stack: list = []  # cycle detection for view expansion

    # --- relations -----------------------------------------------------------
    def analyze(self, sel) -> LogicalPlan:
        if isinstance(sel, ast.SetOp):
            return self._analyze_setop(sel, None, {})
        return self._analyze_select(sel, None, {})

    def _analyze_setop(self, so: ast.SetOp, outer, ctes) -> LogicalPlan:
        ctes = dict(ctes)
        for name, sub in so.ctes:
            ctes[name.lower()] = sub
        plans = [
            self._analyze_setop(s, outer, ctes) if isinstance(s, ast.SetOp)
            else self._analyze_select(s, outer, ctes)
            for s in so.selects
        ]
        arities = {len(p.output_names()) for p in plans}
        if len(arities) != 1:
            raise AnalyzerError(f"UNION inputs have different arities: {arities}")
        # rename every child's outputs to the first child's names (positional)
        names = [n.split(".", 1)[-1] for n in plans[0].output_names()]
        aligned = []
        for p in plans:
            aligned.append(
                LProject(p, tuple(
                    (nm, Col(q)) for nm, q in zip(names, p.output_names())
                ))
            )
        if so.kind in ("intersect", "except"):
            # left-associative n-ary chain: fold pairwise
            plan = aligned[0]
            for rhs in aligned[1:]:
                if so.all:
                    plan = self._setop_all([plan, rhs], names, so.kind)
                else:
                    plan = self._setop_filtered([plan, rhs], names, so.kind)
        else:
            plan = LUnion(tuple(aligned))
            if not so.all:
                plan = LAggregate(
                    plan, tuple((n, Col(n)) for n in names), ()
                )
        order_items = [
            (self._lower_order_expr_union(o, names), o.asc,
             o.nulls_first if o.nulls_first is not None else not o.asc)
            for o in so.order_by
        ]
        if order_items:
            plan = LSort(plan, tuple(order_items),
                         so.limit if so.offset == 0 else None)
            if so.limit is not None and so.offset != 0:
                plan = LLimit(plan, so.limit, so.offset)
        elif so.limit is not None:
            plan = LLimit(plan, so.limit, so.offset)
        return plan

    def _setop_filtered(self, aligned, names, kind):
        """INTERSECT/EXCEPT via union + side-tagged counting: group by all
        columns (NULLs group together — correct set-op NULL semantics, which
        a join-based rewrite would get wrong) and keep groups present on the
        right side or not."""
        # unique synthetic names so user columns can't collide/shadow them
        uid = next(self._ids)
        side_c, cl_c, cr_c = f"__side_{uid}", f"__cl_{uid}", f"__cr_{uid}"
        tagged = []
        for side, p in enumerate(aligned):
            tagged.append(LProject(
                p,
                tuple((n, Col(n)) for n in names) + ((side_c, Lit(side)),),
            ))
        u = LUnion(tuple(tagged))
        agg = LAggregate(
            u,
            tuple((n, Col(n)) for n in names),
            ((cl_c, AggExpr("sum", Call("subtract", Lit(1), Col(side_c)))),
             (cr_c, AggExpr("sum", Col(side_c)))),
        )
        if kind == "intersect":
            pred = Call("and", Call("gt", Col(cl_c), Lit(0)),
                        Call("gt", Col(cr_c), Lit(0)))
        else:
            pred = Call("and", Call("gt", Col(cl_c), Lit(0)),
                        Call("eq", Col(cr_c), Lit(0)))
        filt = LFilter(agg, pred)
        return LProject(filt, tuple((n, Col(n)) for n in names))

    def _setop_all(self, aligned, names, kind):
        """INTERSECT ALL / EXCEPT ALL via window-counted multiplicity
        (reference: be/src/exec/intersect_node.h's hash-counting semantics):
        union both sides tagged 0/1, then over PARTITION BY all columns
        (NULLs group together — window partitioning, not a join, so set-op
        NULL semantics hold) compute cr = whole-partition count of right
        rows and rn = row_number ordered by side (left rows get 1..cl).
        Keep left rows with rn <= cr (INTERSECT ALL -> min(cl, cr) copies)
        or rn > cr (EXCEPT ALL -> max(cl - cr, 0) copies)."""
        uid = next(self._ids)
        side_c, rn_c, cr_c = f"__side_{uid}", f"__rn_{uid}", f"__cr_{uid}"
        tagged = []
        for side, p in enumerate(aligned):
            tagged.append(LProject(
                p,
                tuple((n, Col(n)) for n in names) + ((side_c, Lit(side)),),
            ))
        u = LUnion(tuple(tagged))
        part = tuple(Col(n) for n in names)
        w = LWindow(u, part, (),
                    ((cr_c, "sum", Col(side_c), None, None, None),))
        w = LWindow(w, part, ((Col(side_c), True, False),),
                    ((rn_c, "row_number", None, None, None, None),))
        cmp = "le" if kind == "intersect" else "gt"
        pred = Call("and", Call("eq", Col(side_c), Lit(0)),
                    Call(cmp, Col(rn_c), Col(cr_c)))
        filt = LFilter(w, pred)
        return LProject(filt, tuple((n, Col(n)) for n in names))

    def _lower_order_expr_union(self, o, names):
        e = o.expr
        if isinstance(e, Lit) and isinstance(e.value, int):
            idx = e.value - 1
            if not (0 <= idx < len(names)):
                raise AnalyzerError(f"ORDER BY ordinal {e.value} out of range")
            return Col(names[idx])
        if isinstance(e, ast.RawCol) and e.table is None and e.name in names:
            return Col(e.name)
        raise AnalyzerError(
            "ORDER BY on a UNION must reference output columns by name/ordinal"
        )

    def _analyze_select(
        self, sel: ast.Select, outer: Optional[Scope], ctes: dict
    ) -> LogicalPlan:
        ctes = dict(ctes)
        for name, sub in sel.ctes:
            ctes[name.lower()] = sub

        if sel.from_ is None:
            # FROM-less SELECT (constants, connector probes like SELECT 1):
            # scan the hidden one-row dual table (catalog.get_table resolves
            # "__dual__" outside the user namespace — unlistable, read-only;
            # reference: the FE's constant-expression path in
            # qe/StmtExecutor)
            if any(isinstance(it.expr, ast.Star) for it in sel.items):
                raise AnalyzerError("SELECT * requires a FROM clause")
            plan = LScan("__dual__", "__dual__", ("__one__",))
            scope = Scope([("__dual__", ())], outer)
        else:
            plan, scope = self._analyze_relation(sel.from_, outer, ctes)

        if sel.where is not None:
            pred = self._lower(sel.where, scope, ctes, allow_agg=False)
            if any(isinstance(x, WindowExpr) for x in _walk_expr(pred)):
                raise AnalyzerError("window functions are not allowed in WHERE")
            plan = LFilter(plan, pred)

        # --- aggregate detection --------------------------------------------
        lowered_items = []
        for item in sel.items:
            if isinstance(item.expr, ast.Star):
                for q in self._star_names(scope, item.expr.table):
                    lowered_items.append((q.split(".", 1)[1], Col(q)))
                continue
            e = self._lower(item.expr, scope, ctes, allow_agg=True)
            name = item.alias or self._auto_name(item.expr)
            if any(name == n for n, _ in lowered_items):
                # chunks need unique column names (SQL allows duplicates;
                # values are what matter, readers use positions)
                k = 1
                while any(f"{name}_{k}" == n for n, _ in lowered_items):
                    k += 1
                name = f"{name}_{k}"
            lowered_items.append((name, e))

        group_exprs = []
        for g in sel.group_by:
            if isinstance(g, Lit) and isinstance(g.value, int):
                idx = g.value - 1
                if not (0 <= idx < len(lowered_items)):
                    raise AnalyzerError(f"GROUP BY ordinal {g.value} out of range")
                group_exprs.append(lowered_items[idx][1])
                continue
            if isinstance(g, ast.RawCol) and g.table is None:
                # MySQL extension: GROUP BY may reference a SELECT alias
                # when it doesn't shadow an input column
                hit = next((e for n, e in lowered_items
                            if n.lower() == g.name.lower()), None)
                if hit is not None and scope.resolve_or_none(
                        None, g.name) is None:
                    if any(isinstance(x, AggExpr) for x in _walk_expr(hit)):
                        raise AnalyzerError(
                            f"GROUP BY alias {g.name!r} references an "
                            "aggregate")
                    group_exprs.append(hit)
                    continue
            group_exprs.append(self._lower(g, scope, ctes, allow_agg=False))

        having = (
            self._lower(sel.having, scope, ctes, allow_agg=True)
            if sel.having is not None
            else None
        )
        if having is not None and any(
            isinstance(x, WindowExpr) for x in _walk_expr(having)
        ):
            raise AnalyzerError("window functions are not allowed in HAVING")
        order_items = [
            (self._lower_order_expr(o.expr, lowered_items, scope, ctes), o.asc,
             o.nulls_first if o.nulls_first is not None else not o.asc)
            for o in sel.order_by
        ]

        has_agg = (
            bool(group_exprs)
            or any(_contains_agg(e) for _, e in lowered_items)
            or (having is not None and _contains_agg(having))
        )
        if not group_exprs and any(
            isinstance(x, Call) and x.fn == "grouping"
            for _, e in lowered_items for x in _walk_expr(e)
        ):
            raise AnalyzerError("grouping() requires GROUP BY")

        if has_agg:
            plan, lowered_items, having, order_items = self._build_aggregate(
                plan, group_exprs, lowered_items, having, order_items,
                grouping_mode=sel.rollup,
            )
            if sel.rollup:
                plan = self._grouping_expand(plan, sel.rollup)
            if having is not None:
                plan = LFilter(plan, having)

        visible_names = None
        plan, lowered_items, order_items = self._extract_windows(
            plan, lowered_items, order_items
        )
        # ORDER BY may reference columns that aren't in the select list
        # (hidden sort columns — windows or plain source columns): carry them
        # through the projection and strip them after the sort
        item_names = {n for n, _ in lowered_items}
        hidden = {
            c
            for e, _, _ in order_items
            for c in _cols_of(e)
            if c not in item_names
        }
        if hidden and not sel.distinct:
            visible_names = [n for n, _ in lowered_items]
            lowered_items = lowered_items + [(c, Col(c)) for c in sorted(hidden)]
        elif hidden:
            raise AnalyzerError(
                f"ORDER BY column(s) {sorted(hidden)} must appear in the "
                "select list of a DISTINCT query"
            )

        plan = LProject(plan, tuple(lowered_items))

        if sel.distinct:
            plan = LAggregate(
                plan,
                tuple((n, Col(n)) for n, _ in lowered_items),
                (),
            )

        if order_items:
            limit = sel.limit if sel.offset == 0 else None
            plan = LSort(plan, tuple(order_items), limit)
            if sel.limit is not None and sel.offset != 0:
                plan = LLimit(plan, sel.limit, sel.offset)
        elif sel.limit is not None:
            plan = LLimit(plan, sel.limit, sel.offset)
        if visible_names is not None:
            # drop ORDER-BY-only window columns from the visible output
            plan = LProject(plan, tuple((n, Col(n)) for n in visible_names))
        return plan

    def _analyze_relation(self, rel, outer, ctes):
        if isinstance(rel, ast.TableRef):
            name = rel.name.lower()
            view_sql = getattr(self.catalog, "views", {}).get(name)
            if view_sql is not None and name not in ctes:
                from .parser import parse as _parse

                if name in self._view_stack:
                    raise AnalyzerError(
                        f"cyclic view reference: {' -> '.join(self._view_stack + [name])}"
                    )
                self._view_stack.append(name)
                try:
                    # views resolve against the catalog ONLY: caller CTEs and
                    # outer scopes must not leak into the view body
                    return self._expand_definition(
                        _parse(view_sql), rel.alias or name, None, {}
                    )
                finally:
                    self._view_stack.pop()
            if name in ctes:
                return self._expand_definition(
                    ctes[name], rel.alias or name, outer, ctes
                )
            t = self.catalog.get_table(name)
            if t is None:
                raise AnalyzerError(f"unknown table {rel.name!r}")
            alias = rel.alias or name
            cols = tuple(f.name for f in t.schema)
            scan = LScan(name, alias, cols)
            return scan, Scope([(alias, cols)], outer)
        if isinstance(rel, ast.SubqueryRef):
            if isinstance(rel.select, ast.SetOp):
                sub_plan = self._analyze_setop(rel.select, outer, ctes)
            else:
                sub_plan = self._analyze_select(rel.select, outer, ctes)
            return self._aliased_subplan(sub_plan, rel.alias, outer)
        if isinstance(rel, ast.UnnestRef):
            raise AnalyzerError(
                "unnest() must follow a table in the FROM list "
                "(lateral: FROM t, unnest(t.arr) u(x))")
        if isinstance(rel, ast.JoinRef):
            lplan, lscope = self._analyze_relation(rel.left, outer, ctes)
            if isinstance(rel.right, ast.UnnestRef):
                if rel.kind not in ("cross", "inner") or rel.on is not None:
                    raise AnalyzerError(
                        "unnest() only combines via comma/CROSS JOIN")
                u = rel.right
                e = self._lower(u.expr, lscope, ctes, allow_agg=False)
                out_name = f"{u.alias}.{u.col}"
                plan = LUnnest(lplan, e, out_name)
                scope = Scope(
                    lscope.entries + [(u.alias, (u.col,))], outer)
                return plan, scope
            rplan, rscope = self._analyze_relation(rel.right, outer, ctes)
            scope = Scope(lscope.entries + rscope.entries, outer)
            kind = rel.kind
            cond = None
            if rel.on is not None:
                cond = self._lower(rel.on, scope, ctes, allow_agg=False)
            if kind == "right":
                # normalize RIGHT JOIN to LEFT JOIN with swapped inputs
                lplan, rplan = rplan, lplan
                scope = Scope(rscope.entries + lscope.entries, outer)
                kind = "left"
            return LJoin(lplan, rplan, kind, cond), scope
        raise AnalyzerError(f"unsupported relation {rel!r}")

    def _expand_definition(self, def_ast, alias: str, outer, ctes):
        """Analyze a view/CTE definition AST and expose it under an alias."""
        if isinstance(def_ast, ast.SetOp):
            sub_plan = self._analyze_setop(def_ast, outer, ctes)
        else:
            sub_plan = self._analyze_select(def_ast, outer, ctes)
        return self._aliased_subplan(sub_plan, alias, outer)

    def _aliased_subplan(self, sub_plan: LogicalPlan, alias: str, outer=None):
        """Wrap a subquery plan so its outputs become alias.col. `outer`
        becomes the scope's parent so correlated references THROUGH a
        derived table / CTE alias resolve (e.g. TPC-DS q1's ctr1 inside the
        per-store average subquery); views pass None — their bodies must not
        see the caller's scope."""
        out = sub_plan.output_names()
        base = tuple(n.split(".", 1)[-1] for n in out)
        if len(set(base)) != len(base):
            raise AnalyzerError(f"duplicate column names in subquery {alias}: {base}")
        proj = LProject(
            sub_plan, tuple((f"{alias}.{b}", Col(q)) for b, q in zip(base, out))
        )
        return proj, Scope([(alias, base)], outer)

    def _star_names(self, scope: Scope, table: Optional[str]):
        names = []
        for alias, cols in scope.entries:
            if table is None or alias == table:
                names.extend(f"{alias}.{c}" for c in cols)
        if not names:
            raise AnalyzerError(f"unknown table in star: {table}")
        return names

    # --- expressions ---------------------------------------------------------
    def _lower(self, e: Expr, scope: Scope, ctes, allow_agg: bool) -> Expr:
        if isinstance(e, ast.LambdaExpr):
            # params shadow relation columns inside the body; captured
            # outer columns resolve through the normal scope
            stack = getattr(self, "_lam_params", None)
            if stack is None:
                stack = self._lam_params = []
            stack.append(frozenset(p.lower() for p in e.params))
            try:
                body = self._lower(e.body, scope, ctes, allow_agg=False)
            finally:
                stack.pop()
            return IrLambda(tuple(p.lower() for p in e.params), body)
        if isinstance(e, ast.RawCol):
            stack = getattr(self, "_lam_params", None)
            if stack and e.table is None:
                nm = e.name.lower()
                if any(nm in frame for frame in reversed(stack)):
                    return Col(f"@lam.{nm}")
            q, depth = scope.resolve(e.table, e.name)
            if depth > 0:
                # correlated outer reference: mark with special prefix; the
                # subquery assembler extracts these
                return Col(f"@outer.{q}")
            return Col(q)
        if isinstance(e, Col):
            return e
        if isinstance(e, Lit):
            return e
        if isinstance(e, WindowExpr):
            # window args/keys may contain aggregates in a grouped query
            # (e.g. avg(sum(x)) over (...)); the aggregate builder replaces
            # them with refs to the aggregate's outputs
            arg = (
                self._lower(e.arg, scope, ctes, allow_agg=allow_agg)
                if e.arg is not None else None
            )
            part = tuple(self._lower(p, scope, ctes, allow_agg=allow_agg)
                         for p in e.partition_by)
            order = tuple(
                (self._lower(o, scope, ctes, allow_agg=allow_agg), asc, nf)
                for o, asc, nf in e.order_by
            )
            return WindowExpr(e.fn, arg, part, order, e.offset, e.default,
                              e.frame)
        if isinstance(e, AggExpr):
            if not allow_agg:
                raise AnalyzerError(f"aggregate {e} not allowed here")
            arg = (
                self._lower(e.arg, scope, ctes, allow_agg=False)
                if e.arg is not None
                else None
            )
            def lower_extra(x):
                if isinstance(x, Lit):
                    return x
                if isinstance(x, tuple):  # (expr, asc) order items
                    return (self._lower(x[0], scope, ctes,
                                        allow_agg=False),) + x[1:]
                return self._lower(x, scope, ctes, allow_agg=False)

            extra = tuple(lower_extra(x) for x in e.extra)
            return AggExpr(e.fn, arg, e.distinct, extra)
        if isinstance(e, Call):
            return Call(e.fn, *[self._lower(a, scope, ctes, allow_agg) for a in e.args])
        if isinstance(e, Case):
            whens = tuple(
                (self._lower(c, scope, ctes, allow_agg), self._lower(v, scope, ctes, allow_agg))
                for c, v in e.whens
            )
            orelse = self._lower(e.orelse, scope, ctes, allow_agg) if e.orelse is not None else None
            return Case(whens, orelse)
        if isinstance(e, Cast):
            return Cast(self._lower(e.arg, scope, ctes, allow_agg), e.to)
        if isinstance(e, InList):
            return InList(self._lower(e.arg, scope, ctes, allow_agg), e.values, e.negated)
        if isinstance(e, ast.Subquery):
            plan, corr = self._analyze_subquery(e.select, scope, ctes)
            return ScalarSubquery(plan, corr)
        if isinstance(e, ast.Exists):
            plan, corr = self._analyze_subquery(e.select, scope, ctes)
            return SemiJoinMark(plan, corr, None, None, e.negated)
        if isinstance(e, ast.InSubquery):
            probe = self._lower(e.arg, scope, ctes, allow_agg=False)
            plan, corr = self._analyze_subquery(e.select, scope, ctes)
            inner = plan.output_names()
            if len(inner) != 1:
                raise AnalyzerError("IN subquery must produce one column")
            return SemiJoinMark(plan, corr, probe, inner[0], e.negated)
        if isinstance(e, ast.RawFunc):
            if e.name == "grouping" and len(e.args) == 1:
                if not allow_agg:
                    raise AnalyzerError(
                        "grouping() is only allowed in grouped select "
                        "items / HAVING / ORDER BY")
                # resolved to a 0/1 level marker by the aggregate builder
                return Call("grouping",
                            self._lower(e.args[0], scope, ctes, allow_agg=False))
            from ..runtime.udf import get_udf

            if get_udf(e.name) is not None:
                return Call(e.name.lower(),
                            *[self._lower(a, scope, ctes, allow_agg=False)
                              for a in e.args])
            raise AnalyzerError(f"unknown function {e.name!r}")
        if isinstance(e, ast.Star):
            raise AnalyzerError("* only allowed as a top-level select item")
        raise AnalyzerError(f"cannot analyze expression {e!r}")

    def _lower_order_expr(self, e, lowered_items, scope, ctes):
        # ORDER BY may reference select aliases or ordinals
        if isinstance(e, Lit) and isinstance(e.value, int):
            idx = e.value - 1
            if not (0 <= idx < len(lowered_items)):
                raise AnalyzerError(f"ORDER BY ordinal {e.value} out of range")
            return Col(lowered_items[idx][0])
        if isinstance(e, ast.RawCol) and e.table is None:
            for n, _ in lowered_items:
                if n == e.name:
                    return Col(n)
        lowered = self._lower(e, scope, ctes, allow_agg=True)
        # exact match against a select item -> reference it by name
        for n, le in lowered_items:
            if le == lowered:
                return Col(n)
        return lowered

    def _analyze_subquery(self, sel: ast.Select, outer_scope: Scope, ctes):
        """Analyze a subquery; extract correlated equality pairs.

        The subquery plan may contain Col("@outer.x") references; we pull
        equality predicates of the form inner_col = @outer.x out of filters
        (the optimizer turns them into join keys)."""
        if isinstance(sel, ast.SetOp):
            plan = self._analyze_setop(sel, outer_scope, ctes)
        else:
            plan = self._analyze_select(sel, outer_scope, ctes)
        corr = _extract_correlations(plan)
        return plan, corr

    # --- aggregates ----------------------------------------------------------
    def _build_aggregate(self, plan, group_exprs, items, having, order_items,
                         grouping_mode=False):
        """Split select items into (pre-projection, aggregate, post-projection)."""
        aggs = {}
        pre = {}
        grouping_refs = set()  # __grouping_i columns referenced via grouping()

        def agg_name(a: AggExpr) -> str:
            for n, existing in aggs.items():
                if existing == a:
                    return n
            n = f"agg_{len(aggs)}"
            aggs[n] = a
            return n

        group_named = []
        for i, g in enumerate(group_exprs):
            if isinstance(g, Col):
                group_named.append((g.name, g))
            else:
                group_named.append((f"gexpr_{i}", g))

        def replace(e: Expr) -> Expr:
            # replace whole-group-expr matches and aggregates by refs
            for gname, gexpr in group_named:
                if e == gexpr:
                    return Col(gname)
            if isinstance(e, AggExpr):
                return Col(agg_name(e))
            if isinstance(e, Call) and e.fn in ("grouping", "grouping_id"):
                if not grouping_mode:
                    return Lit(0)  # no ROLLUP/CUBE/SETS: always base level

                def marker(arg):
                    for i, (gname, gexpr) in enumerate(group_named):
                        if arg == gexpr or (isinstance(arg, Col)
                                            and arg.name == gname):
                            grouping_refs.add(f"__grouping_{i}")
                            return Col(f"__grouping_{i}")
                    raise AnalyzerError(
                        f"{e.fn}() argument {arg!r} is not a GROUP BY key")

                if e.fn == "grouping":
                    return marker(e.args[0])
                # grouping_id(a, b, ...) = the markers as a bit field,
                # first argument most significant (reference semantics)
                out = None
                for j, arg in enumerate(e.args):
                    bit = Call("multiply", marker(arg),
                               Lit(1 << (len(e.args) - 1 - j)))
                    out = bit if out is None else Call("add", out, bit)
                return out if out is not None else Lit(0)
            if isinstance(e, Call):
                return Call(e.fn, *[replace(a) for a in e.args])
            if isinstance(e, Case):
                return Case(
                    tuple((replace(c), replace(v)) for c, v in e.whens),
                    replace(e.orelse) if e.orelse is not None else None,
                )
            if isinstance(e, Cast):
                return Cast(replace(e.arg), e.to)
            if isinstance(e, InList):
                return InList(replace(e.arg), e.values, e.negated)
            if isinstance(e, Col):
                return e
            if isinstance(e, Lit):
                return e
            if isinstance(e, WindowExpr):
                return WindowExpr(
                    e.fn,
                    replace(e.arg) if e.arg is not None else None,
                    tuple(replace(p) for p in e.partition_by),
                    tuple((replace(o), a, nf) for o, a, nf in e.order_by),
                    e.offset, e.default, e.frame,
                )
            if isinstance(e, IrLambda):
                # captured outer columns must resolve through group keys
                # like any other reference; params (@lam.*) pass through
                return IrLambda(e.params, replace(e.body))
            if isinstance(e, (ScalarSubquery, SemiJoinMark)):
                return e
            raise AnalyzerError(f"cannot use {e!r} in aggregate query")

        new_items = [(n, replace(e)) for n, e in items]
        new_having = replace(having) if having is not None else None
        new_order = [(replace(e), asc, nf) for e, asc, nf in order_items]

        # validate: non-agg select items must now only reference group keys/aggs
        allowed = {n for n, _ in group_named} | set(aggs) | grouping_refs
        for n, e in new_items:
            for c in _cols_of(e):
                if c not in allowed:
                    raise AnalyzerError(
                        f"column {c!r} must appear in GROUP BY or an aggregate"
                    )

        agg_node = LAggregate(plan, tuple(group_named), tuple(aggs.items()))
        return agg_node, new_items, new_having, new_order

    def _extract_windows(self, plan, items, order_items):
        """Pull WindowExpr subtrees out of select/order expressions into
        LWindow nodes (one per distinct (partition, order) spec)."""
        specs = {}  # (partition, order) -> list[(name, fn, arg)]
        mapping = {}  # WindowExpr -> Col name

        def collect(e):
            if isinstance(e, WindowExpr):
                if e in mapping:
                    return
                name = f"win_{len(mapping)}"
                mapping[e] = name
                specs.setdefault((e.partition_by, e.order_by), []).append(
                    (name, e.fn, e.arg, e.offset, e.default, e.frame)
                )
                return
            if isinstance(e, Call):
                for a in e.args:
                    collect(a)
            elif isinstance(e, Case):
                for c, v in e.whens:
                    collect(c)
                    collect(v)
                if e.orelse is not None:
                    collect(e.orelse)
            elif isinstance(e, Cast):
                collect(e.arg)
            elif isinstance(e, InList):
                collect(e.arg)

        for _, e in items:
            collect(e)
        for e, _, _ in order_items:
            collect(e)
        if not mapping:
            return plan, items, order_items

        def subst(e):
            if isinstance(e, WindowExpr):
                return Col(mapping[e])
            if isinstance(e, Call):
                return Call(e.fn, *[subst(a) for a in e.args])
            if isinstance(e, Case):
                return Case(
                    tuple((subst(c), subst(v)) for c, v in e.whens),
                    subst(e.orelse) if e.orelse is not None else None,
                )
            if isinstance(e, Cast):
                return Cast(subst(e.arg), e.to)
            if isinstance(e, InList):
                return InList(subst(e.arg), e.values, e.negated)
            return e

        for (part, order), funcs in specs.items():
            plan = LWindow(plan, part, order, tuple(funcs))
        new_items = [(n, subst(e)) for n, e in items]
        new_order = [(subst(e), a, nf) for e, a, nf in order_items]
        return plan, new_items, new_order

    def _grouping_expand(self, agg, mode) -> LogicalPlan:
        """GROUP BY ROLLUP/CUBE/GROUPING SETS -> UNION ALL of levels, each
        re-aggregated from the finest level (shared subtree; the physical
        emitters memoize node emission so the finest agg computes once).
        Dropped keys become typed NULL columns via null_of(); every level
        also emits __grouping_i 0/1 markers for grouping(). AVG splits into
        sum+count at the base so coarser levels merge exactly.
        Reference: fe-core/.../sql/ast/GroupByClause.java grouping types."""
        if not isinstance(agg, LAggregate) or not agg.group_by:
            return agg
        n = len(agg.group_by)
        if mode[0] == "rollup":
            subsets = [tuple(range(k)) for k in range(n, -1, -1)]
        elif mode[0] == "cube":
            if n > 6:
                raise AnalyzerError("CUBE over more than 6 keys")
            subsets = [
                tuple(i for i in range(n) if (mask >> i) & 1)
                for mask in range((1 << n) - 1, -1, -1)
            ]
        else:  # ("sets", index-subsets)
            subsets = [tuple(s) for s in mode[1]]
            for s in subsets:
                if any(not (0 <= i < n) for i in s):
                    raise AnalyzerError("GROUPING SETS key out of range")

        # split AVG into mergeable sum+count parts at the base level
        base_aggs, avg_map = [], {}
        for nm, a in agg.aggs:
            if a.distinct:
                raise AnalyzerError(
                    "DISTINCT aggregates with ROLLUP/CUBE/GROUPING SETS "
                    "are not supported yet")
            if a.fn == "avg":
                sn, cn = f"__avs_{nm}", f"__avc_{nm}"
                base_aggs.append((sn, AggExpr("sum", a.arg)))
                base_aggs.append((cn, AggExpr("count", a.arg)))
                avg_map[nm] = (sn, cn)
            else:
                base_aggs.append((nm, a))
        base = LAggregate(agg.child, agg.group_by, tuple(base_aggs))

        def merge_of(name, a):
            if a.fn in ("count", "count_star", "sum"):
                return AggExpr("sum", Col(name))
            if a.fn in ("min", "max"):
                return AggExpr(a.fn, Col(name))
            raise AnalyzerError(
                f"{a.fn} with ROLLUP/CUBE/GROUPING SETS is not supported yet")

        def avg_result(nm):
            sn, cn = avg_map[nm]
            from .. import types as T

            return Call("divide", Cast(Col(sn), T.DOUBLE), Col(cn))

        full = tuple(range(n))
        levels = []
        # ROLLUP levels are PREFIXES in decreasing order: level k can
        # re-aggregate level k+1's (10-100x smaller) output instead of the
        # base — sum/count/min/max merges are associative, and dropped-key
        # ride-alongs are either the finer level's group keys or its own
        # min() outputs. TPC-DS q67: 8 re-aggregations over the 440k-group
        # base become one 440k re-agg plus 7 tiny ones. CUBE/GROUPING SETS
        # subsets aren't nested, so they keep aggregating from the base.
        chain = mode[0] == "rollup"
        prev_lvl = None
        for subset in subsets:
            sset = frozenset(subset)
            if tuple(sorted(subset)) == full:
                lvl = base
            else:
                sub_group = tuple(
                    (nm, Col(nm))
                    for i, (nm, _) in enumerate(agg.group_by) if i in sset)
                dropped = [
                    nm for i, (nm, _) in enumerate(agg.group_by)
                    if i not in sset]
                # dropped keys ride along (any value) so null_of() can type
                # the NULL output columns
                sub_aggs = tuple(
                    (nm, merge_of(nm, a)) for nm, a in base_aggs
                ) + tuple((nm, AggExpr("min", Col(nm))) for nm in dropped)
                src = prev_lvl if (chain and prev_lvl is not None) else base
                lvl = LAggregate(src, sub_group, sub_aggs)
            prev_lvl = lvl
            proj = tuple(
                (nm, Col(nm) if i in sset else Call("null_of", Col(nm)))
                for i, (nm, _) in enumerate(agg.group_by)
            ) + tuple(
                (nm, avg_result(nm) if nm in avg_map else Col(nm))
                for nm, _ in agg.aggs
            ) + tuple(
                (f"__grouping_{i}", Lit(0 if i in sset else 1))
                for i in range(n)
            )
            levels.append(LProject(lvl, proj))
        return LUnion(tuple(levels))

    @staticmethod
    def _auto_name(e) -> str:
        if isinstance(e, ast.RawCol):
            return e.name
        r = repr(e)
        return r if len(r) <= 40 else r[:37] + "..."


def _walk_expr(e: Expr):
    from ..exprs.ir import walk

    yield from walk(e)


def _contains_agg(e: Expr) -> bool:
    if isinstance(e, AggExpr):
        return True
    if isinstance(e, Call):
        return any(_contains_agg(a) for a in e.args)
    if isinstance(e, Case):
        return any(
            _contains_agg(c) or _contains_agg(v) for c, v in e.whens
        ) or (e.orelse is not None and _contains_agg(e.orelse))
    if isinstance(e, Cast):
        return _contains_agg(e.arg)
    if isinstance(e, InList):
        return _contains_agg(e.arg)
    if isinstance(e, WindowExpr):
        # an aggregate inside a window arg/key makes the query grouped
        # (e.g. rank() over (order by sum(x)) with no GROUP BY)
        return (
            (e.arg is not None and _contains_agg(e.arg))
            or any(_contains_agg(p) for p in e.partition_by)
            or any(_contains_agg(o) for o, _, _ in e.order_by)
        )
    return False


def _cols_of(e: Expr):
    if isinstance(e, Col):
        if not e.name.startswith("@lam."):
            yield e.name
    elif isinstance(e, IrLambda):
        yield from _cols_of(e.body)
    elif isinstance(e, Call):
        for a in e.args:
            yield from _cols_of(a)
    elif isinstance(e, Case):
        for c, v in e.whens:
            yield from _cols_of(c)
            yield from _cols_of(v)
        if e.orelse is not None:
            yield from _cols_of(e.orelse)
    elif isinstance(e, Cast):
        yield from _cols_of(e.arg)
    elif isinstance(e, InList):
        yield from _cols_of(e.arg)
    elif isinstance(e, WindowExpr):
        if e.arg is not None:
            yield from _cols_of(e.arg)
        for p in e.partition_by:
            yield from _cols_of(p)
        for o, _, _ in e.order_by:
            yield from _cols_of(o)


def _extract_correlations(plan: LogicalPlan) -> tuple:
    """Find Col('@outer.x') equality pairs in the plan's filters."""
    from .logical import walk_plan

    pairs = []
    for node in walk_plan(plan):
        if isinstance(node, LFilter):
            for conj in _conjuncts(node.predicate):
                if (
                    isinstance(conj, Call)
                    and conj.fn == "eq"
                    and len(conj.args) == 2
                ):
                    a, b = conj.args
                    if isinstance(a, Col) and a.name.startswith("@outer."):
                        if isinstance(b, Col):
                            pairs.append((a.name[len("@outer."):], b.name))
                    elif isinstance(b, Col) and b.name.startswith("@outer."):
                        if isinstance(a, Col):
                            pairs.append((b.name[len("@outer."):], a.name))
    return tuple(pairs)


def _conjuncts(e: Expr):
    if isinstance(e, Call) and e.fn == "and":
        for a in e.args:
            yield from _conjuncts(a)
    else:
        yield e
