"""Recursive-descent SQL parser.

Reference behavior: fe SqlParser (fe-core/.../sql/parser/SqlParser.java:70,
grammar fe/fe-grammar/StarRocks.g4). Produces ast.py statements with exprs.ir
scalar expressions (unresolved RawCol/RawFunc forms).
"""

from __future__ import annotations

from ..exprs import functions_ext as _fext  # noqa: F401 (fills the registry)
from ..exprs.compile import _FUNCTIONS as _SCALAR_REGISTRY
from ..exprs.ir import AggExpr, Call, Case, Cast, Expr, InList, Lit, WindowExpr
from .. import types as T
from . import ast
from .lexer import Token, tokenize


class ParseError(ValueError):
    pass


def _num_lit(text: str):
    """Non-integer numeric literal value: float unless the digits exceed
    float64's exact range — then decimal.Decimal (DECIMAL(38) literals must
    survive parsing losslessly)."""
    digits = sum(ch.isdigit() for ch in text)
    if digits <= 15 or "e" in text.lower():
        return float(text)
    import decimal

    return decimal.Decimal(text)


AGG_FUNCS = {"sum", "count", "avg", "min", "max",
             "stddev_pop", "stddev_samp", "var_pop", "var_samp",
             "covar_pop", "covar_samp", "corr",
             "percentile_cont", "percentile_disc", "group_concat",
             "array_agg",
             "approx_count_distinct", "hll_sketch", "hll_union",
             "hll_union_agg", "hll_raw_agg",
             "bitmap_agg", "bitmap_union", "bitmap_union_count",
             "intersect_count"}
# aliases resolving to a canonical aggregate (MySQL/reference naming:
# std/stddev/variance are population forms; any_value picks an arbitrary
# row — min is a valid choice; ndv answers exactly, approx_count_distinct
# rides the HLL sketch like the reference)
AGG_ALIASES = {
    "std": "stddev_pop", "stddev": "stddev_pop", "variance": "var_pop",
    "any_value": "min", "arbitrary": "min",
    "bool_and": "min", "bool_or": "max",
}
# aggregates whose second positional argument is part of the spec
AGG_EXTRA_ARG = {"covar_pop", "covar_samp", "corr",
                 "percentile_cont", "percentile_disc"}

# scalar function name -> registry name (None = same)
SCALAR_FUNCS = {
    "year": "year", "month": "month", "day": "day",
    "substr": "substr", "substring": "substr",
    "upper": "upper", "lower": "lower", "abs": "abs",
    "coalesce": "coalesce", "if": "if", "mod": "mod",
    "starts_with": "starts_with", "ends_with": "ends_with",
    "concat": "concat", "length": "length", "char_length": "length",
    "trim": "trim", "ltrim": "ltrim", "rtrim": "rtrim", "replace": "replace",
    "round": "round", "floor": "floor", "ceil": "ceil", "ceiling": "ceil",
    "sqrt": "sqrt", "power": "power", "pow": "power", "exp": "exp", "ln": "ln",
    "greatest": "greatest", "least": "least", "datediff": "datediff",
    "dayofweek": "dayofweek", "quarter": "quarter", "null_of": "null_of",
    "date_add_days": "date_add_days", "date_add_months": "date_add_months",
}


class Parser:
    def __init__(self, sql: str):
        self.toks = tokenize(sql)
        self._sql_text = sql
        self.i = 0

    # --- token helpers -------------------------------------------------------
    def peek(self, k: int = 0) -> Token:
        return self.toks[min(self.i + k, len(self.toks) - 1)]

    def next(self) -> Token:
        t = self.toks[self.i]
        self.i += 1
        return t

    def at_kw(self, *words) -> bool:
        t = self.peek()
        return t.kind == "kw" and t.value in words

    def at_op(self, *ops) -> bool:
        t = self.peek()
        return t.kind == "op" and t.value in ops

    def accept_kw(self, *words) -> bool:
        if self.at_kw(*words):
            self.next()
            return True
        return False

    def accept_op(self, *ops) -> bool:
        if self.at_op(*ops):
            self.next()
            return True
        return False

    def expect_kw(self, word):
        if not self.accept_kw(word):
            raise ParseError(f"expected {word.upper()} at {self.peek().value!r} (pos {self.peek().pos})")

    def expect_op(self, op):
        if not self.accept_op(op):
            raise ParseError(f"expected {op!r} at {self.peek().value!r} (pos {self.peek().pos})")

    # statement-dispatch words that remain valid identifiers elsewhere
    SOFT_KEYWORDS = frozenset({
        "year", "month", "day", "date", "first", "last", "tables", "values",
        "show", "key", "primary", "update", "set", "delete", "truncate",
        "partitions", "less", "than", "maxvalue",
        "describe", "desc", "view", "materialized", "refresh",
        "row", "rows", "range", "following", "unbounded", "preceding",
        "current",
    })

    def expect_ident(self) -> str:
        t = self.peek()
        # permit non-reserved keywords as identifiers where unambiguous
        if t.kind == "ident" or (t.kind == "kw" and t.value in self.SOFT_KEYWORDS):
            self.next()
            return t.value
        raise ParseError(f"expected identifier at {t.value!r} (pos {t.pos})")

    # --- entry ---------------------------------------------------------------
    def parse_statement(self):
        if self.at_kw("explain"):
            self.next()
            analyze = self.accept_kw("analyze")
            return ast.Explain(self.parse_statement(), analyze)
        if self.at_kw("select", "with") or self._at_paren_select():
            s = self.parse_select()
            self.accept_op(";")
            return s
        if self.at_kw("create"):
            return self.parse_create()
        if self.at_kw("insert"):
            return self.parse_insert()
        if self.at_kw("drop"):
            return self.parse_drop()
        if self.accept_kw("update"):
            name = self.parse_table_name()
            self.expect_kw("set")
            assigns = []
            while True:
                col_name = self.expect_ident()
                self.expect_op("=")
                assigns.append((col_name, self.parse_expr()))
                if not self.accept_op(","):
                    break
            where = None
            if self.accept_kw("where"):
                where = self.parse_expr()
            self.accept_op(";")
            return ast.Update(name, tuple(assigns), where)
        if self.accept_kw("set"):
            name = self.expect_ident()
            self.expect_op("=")
            neg = self.accept_op("-")
            t = self.next()
            if t.kind == "string":
                val = t.value
            elif t.kind == "number":
                val = _num_lit(t.value) if "." in t.value else int(t.value)
            elif t.kind == "kw" and t.value in ("true", "false"):
                val = t.value == "true"
            else:
                val = t.value
            if neg:
                val = -val
            self.accept_op(";")
            return ast.SetVar(name, val)
        if self.accept_kw("refresh"):
            self.accept_kw("materialized")
            self.expect_kw("view")
            name = self.expect_ident()
            self.accept_op(";")
            return ast.RefreshView(name)
        if self.accept_kw("delete"):
            self.expect_kw("from")
            name = self.parse_table_name()
            where = None
            if self.accept_kw("where"):
                where = self.parse_expr()
            self.accept_op(";")
            return ast.Delete(name, where)
        if self.accept_kw("truncate"):
            self.accept_kw("table")
            name = self.parse_table_name()
            self.accept_op(";")
            return ast.Delete(name, None)
        if self.peek().kind == "ident" and self.peek().value.lower() in (
                "grant", "revoke"):
            verb = self.next().value.lower()
            privs = []
            while True:
                t = self.next()
                p = t.value.lower()
                if p not in ("select", "insert", "update", "delete", "all"):
                    raise ParseError(f"unknown privilege {t.value!r}")
                privs.append(p)
                if not self.accept_op(","):
                    break
            if privs == ["all"]:
                if (self.peek().kind == "ident"
                        and self.peek().value.lower() == "privileges"):
                    self.next()
                privs = ["select", "insert", "update", "delete"]
            self.expect_kw("on")
            if self.accept_op("*"):
                table = "*"
            else:
                table = self.parse_table_name()
            kw = self.next().value.lower()  # TO / FROM
            if kw not in ("to", "from"):
                raise ParseError(f"expected TO/FROM, got {kw!r}")
            user = self._parse_user_name()
            self.accept_op(";")
            node = ast.Grant if verb == "grant" else ast.Revoke
            return node(tuple(privs), table, user)
        if (self.peek().kind == "ident"
                and self.peek().value.lower() == "kill"):
            self.next()
            if (self.peek().kind in ("ident", "kw")
                    and self.peek().value.lower() in ("query", "connection")):
                self.next()
            t = self.next()
            if t.kind != "number":
                raise ParseError(
                    f"expected a query id after KILL, got {t.value!r}")
            self.accept_op(";")
            return ast.KillQuery(int(t.value))
        if (self.peek().kind == "ident"
                and self.peek().value.lower() == "admin"):
            self.next()
            if (self.peek().kind == "ident"
                    and self.peek().value.lower() == "diagnose"):
                self.next()
                self.accept_op(";")
                return ast.AdminDiagnose()
            self.expect_kw("set")
            word = self.expect_ident()
            if word.lower() not in ("failpoint", "alert", "ingest_job"):
                raise ParseError(
                    f"unsupported ADMIN SET target {word!r} "
                    "(only 'failpoint', 'alert', or 'ingest_job')")
            t = self.next()
            if t.kind != "string":
                raise ParseError(
                    f"expected a quoted {word.lower()} name")
            self.expect_op("=")
            v = self.next()
            if v.kind != "string":
                raise ParseError(
                    f"expected a quoted {word.lower()} value")
            self.accept_op(";")
            if word.lower() == "alert":
                return ast.AdminSetAlert(t.value, v.value)
            if word.lower() == "ingest_job":
                return ast.AdminSetIngestJob(t.value, v.value)
            return ast.AdminSetFailpoint(t.value, v.value)
        if self.accept_kw("show"):
            if (self.peek().kind == "ident"
                    and self.peek().value.lower() == "processlist"):
                self.next()
                self.accept_op(";")
                return ast.ShowProcesslist()
            if (self.peek().kind == "ident"
                    and self.peek().value.lower() == "workload"):
                self.next()
                self.accept_op(";")
                return ast.ShowWorkload()
            if (self.peek().kind == "ident"
                    and self.peek().value.lower() == "grants"):
                self.next()
                user = None
                if (self.peek().kind == "ident"
                        and self.peek().value.lower() == "for"):
                    self.next()
                    user = self._parse_user_name()
                self.accept_op(";")
                return ast.ShowGrants(user)
            if self.accept_kw("create"):
                self.expect_kw("table")
                name = self.parse_table_name()
                self.accept_op(";")
                return ast.ShowCreate(name)
            if self.accept_kw("partitions"):
                self.expect_kw("from")
                name = self.parse_table_name()
                self.accept_op(";")
                return ast.ShowPartitions(name)
            if (self.peek().kind == "ident"
                    and self.peek().value.lower() == "profile"):
                self.next()
                qid = None
                if (self.peek().kind in ("ident", "kw")
                        and self.peek().value.lower() == "for"):
                    self.next()
                    if (self.peek().kind in ("ident", "kw")
                            and self.peek().value.lower() == "query"):
                        self.next()
                    t = self.next()
                    if t.kind != "number":
                        raise ParseError(
                            "expected a query id after "
                            f"SHOW PROFILE FOR QUERY, got {t.value!r}")
                    qid = int(t.value)
                self.accept_op(";")
                return ast.ShowProfile(qid)
            if (self.peek().kind == "ident"
                    and self.peek().value.lower() == "resource"):
                self.next()
                g = self.next()
                if g.value.lower() != "groups":
                    raise ParseError("expected GROUPS after SHOW RESOURCE")
                self.accept_op(";")
                return ast.ShowResourceGroups()
            full = self.accept_kw("full")
            if (self.peek().kind == "ident"
                    and self.peek().value.lower() == "processlist"):
                self.next()
                self.accept_op(";")
                return ast.ShowProcesslist()
            self.expect_kw("tables")
            self.accept_op(";")
            return ast.ShowTables(full)
        if (self.peek().kind == "ident"
                and self.peek().value.lower() == "alter"):
            self.next()
            self.expect_kw("table")
            name = self.parse_table_name()
            word = self.next().value.lower()
            if word == "add":
                if (self.peek().kind in ("ident", "kw")
                        and self.peek().value.lower() == "column"):
                    self.next()
                cname = self.expect_ident()
                t = self.parse_type_name()
                nullable = True
                if self.accept_kw("not"):
                    self.expect_kw("null")
                    nullable = False
                self.accept_op(";")
                return ast.AlterTable(name, "add", cname, t, nullable)
            if word == "drop":
                if (self.peek().kind in ("ident", "kw")
                        and self.peek().value.lower() == "column"):
                    self.next()
                cname = self.expect_ident()
                self.accept_op(";")
                return ast.AlterTable(name, "drop", cname)
            raise ParseError(f"unsupported ALTER TABLE action {word!r}")
        if self.at_kw("describe", "desc"):
            self.next()
            name = self.parse_table_name()
            self.accept_op(";")
            return ast.Describe(name)
        raise ParseError(f"unsupported statement start {self.peek().value!r}")

    def parse_table_name(self) -> str:
        name = self.expect_ident()
        if self.accept_op("."):
            name = f"{name}.{self.expect_ident()}"
        return name

    # --- SELECT --------------------------------------------------------------
    def _at_paren_select(self) -> bool:
        """True at '(' whose first non-'(' token is SELECT/WITH (a
        parenthesized select / set-op chain)."""
        if not self.at_op("("):
            return False
        k = 0
        while self.peek(k).kind == "op" and self.peek(k).value == "(":
            k += 1
        return (self.peek(k).kind == "kw"
                and self.peek(k).value in ("select", "with"))

    def _parse_set_operand(self):
        """One operand of a set-op chain: a SELECT core, or a parenthesized
        select/chain. Returns (node, was_parenthesized)."""
        if self._at_paren_select():
            self.next()
            sub = self.parse_select()
            self.expect_op(")")
            return sub, True
        return self.parse_select_core(), False

    def parse_select(self):
        """SELECT core optionally followed by UNION [ALL] chains."""
        first, first_paren = self._parse_set_operand()
        if not self.at_kw("union", "intersect", "except"):
            if first_paren and self.at_kw("order", "limit"):
                # (select ...) order by ... — hoist trailing clauses
                order_by, limit, offset = self._parse_trailing_order_limit()
                return ast.SetOp((first,), True, "union", order_by, limit,
                                 offset, first.ctes)
            return first
        selects = [first]
        all_flags = []
        kinds = []
        last_paren = first_paren
        while self.at_kw("union", "intersect", "except"):
            kinds.append(self.next().value)
            all_flags.append(self.accept_kw("all"))
            s, last_paren = self._parse_set_operand()
            selects.append(s)
        if len(set(kinds)) > 1:
            raise ParseError("mixing UNION/INTERSECT/EXCEPT is unsupported")
        if len(set(all_flags)) > 1:
            k = kinds[0].upper()
            raise ParseError(f"mixing {k} and {k} ALL is unsupported")
        if last_paren:
            # parenthesized last operand keeps its own clauses; outer
            # ORDER BY / LIMIT may follow the chain
            order_by, limit, offset = self._parse_trailing_order_limit()
        else:
            # order/limit parsed into the LAST core bind to the whole chain
            last = selects[-1]
            order_by, limit, offset = last.order_by, last.limit, last.offset
            selects[-1] = ast.Select(
                last.items, last.from_, last.where, last.group_by,
                last.having, (), None, 0, last.distinct, last.ctes,
                last.rollup,
            )
        return ast.SetOp(
            tuple(selects), all_flags[0], kinds[0], order_by, limit, offset,
            selects[0].ctes,
        )

    def _parse_trailing_order_limit(self):
        order_by = ()
        limit = None
        offset = 0
        if self.accept_kw("order"):
            self.expect_kw("by")
            o = [self.parse_order_item()]
            while self.accept_op(","):
                o.append(self.parse_order_item())
            order_by = tuple(o)
        if self.accept_kw("limit"):
            limit = int(self.next().value)
            if self.accept_op(","):
                offset = limit
                limit = int(self.next().value)
            elif self.accept_kw("offset"):
                offset = int(self.next().value)
        return order_by, limit, offset

    def parse_select_core(self) -> ast.Select:
        ctes = ()
        if self.accept_kw("with"):
            items = []
            while True:
                name = self.expect_ident()
                self.expect_kw("as") if self.at_kw("as") else None
                self.expect_op("(")
                sub = self.parse_select()
                self.expect_op(")")
                items.append((name, sub))
                if not self.accept_op(","):
                    break
            ctes = tuple(items)
        self.expect_kw("select")
        distinct = self.accept_kw("distinct")
        self.accept_kw("all")
        items = [self.parse_select_item()]
        while self.accept_op(","):
            items.append(self.parse_select_item())
        from_ = None
        if self.accept_kw("from"):
            from_ = self.parse_table_refs()
        where = None
        if self.accept_kw("where"):
            where = self.parse_expr()
        group_by = ()
        rollup = False
        if self.accept_kw("group"):
            self.expect_kw("by")
            w = self.peek()
            word = w.value.lower() if w.kind == "ident" else None
            nxt = self.peek(1)
            if (word in ("rollup", "cube")
                    and nxt.kind == "op" and nxt.value == "("):
                self.next()
                self.next()
                rollup = (word,)
                g = [self.parse_expr()]
                while self.accept_op(","):
                    g.append(self.parse_expr())
                self.expect_op(")")
            elif (word == "grouping" and nxt.kind == "ident"
                    and nxt.value.lower() == "sets"):
                self.next()
                self.next()
                self.expect_op("(")
                set_exprs = []
                while True:
                    cur = []
                    if self.accept_op("("):
                        if not self.at_op(")"):
                            cur.append(self.parse_expr())
                            while self.accept_op(","):
                                cur.append(self.parse_expr())
                        self.expect_op(")")
                    else:
                        cur.append(self.parse_expr())
                    set_exprs.append(tuple(cur))
                    if not self.accept_op(","):
                        break
                self.expect_op(")")
                g = []
                for se in set_exprs:
                    for e in se:
                        if e not in g:
                            g.append(e)
                rollup = ("sets", tuple(
                    tuple(g.index(e) for e in se) for se in set_exprs))
            else:
                g = [self.parse_expr()]
                while self.accept_op(","):
                    g.append(self.parse_expr())
            group_by = tuple(g)
        having = None
        if self.accept_kw("having"):
            having = self.parse_expr()
        order_by, limit, offset = self._parse_trailing_order_limit()
        return ast.Select(
            tuple(items), from_, where, group_by, having, tuple(order_by),
            limit, offset, distinct, ctes, rollup,
        )

    def parse_select_item(self) -> ast.SelectItem:
        if self.at_op("*"):
            self.next()
            return ast.SelectItem(ast.Star())
        # qualified star: ident.*
        if (
            self.peek().kind == "ident"
            and self.peek(1).kind == "op"
            and self.peek(1).value == "."
            and self.peek(2).kind == "op"
            and self.peek(2).value == "*"
        ):
            t = self.next().value
            self.next()
            self.next()
            return ast.SelectItem(ast.Star(t))
        e = self.parse_expr()
        alias = None
        if self.accept_kw("as"):
            alias = self.expect_ident()
        elif self.peek().kind == "ident":
            alias = self.next().value
        return ast.SelectItem(e, alias)

    def parse_order_item(self) -> ast.OrderItem:
        e = self.parse_expr()
        asc = True
        if self.accept_kw("desc"):
            asc = False
        else:
            self.accept_kw("asc")
        nulls_first = None
        if self.accept_kw("nulls"):
            if self.accept_kw("first"):
                nulls_first = True
            else:
                self.expect_kw("last")
                nulls_first = False
        return ast.OrderItem(e, asc, nulls_first)

    # --- FROM ----------------------------------------------------------------
    def parse_table_refs(self):
        left = self.parse_table_primary()
        while True:
            if self.accept_op(","):
                right = self.parse_table_primary()
                left = ast.JoinRef(left, right, "cross", None)
                continue
            kind = None
            if self.accept_kw("inner"):
                kind = "inner"
                self.expect_kw("join")
            elif self.accept_kw("left"):
                self.accept_kw("outer")
                kind = "left"
                self.expect_kw("join")
            elif self.accept_kw("right"):
                self.accept_kw("outer")
                kind = "right"
                self.expect_kw("join")
            elif self.accept_kw("full"):
                self.accept_kw("outer")
                kind = "full"
                self.expect_kw("join")
            elif self.accept_kw("cross"):
                kind = "cross"
                self.expect_kw("join")
            elif self.accept_kw("join"):
                kind = "inner"
            if kind is None:
                return left
            right = self.parse_table_primary()
            on = None
            if kind != "cross":
                self.expect_kw("on")
                on = self.parse_expr()
            left = ast.JoinRef(left, right, kind, on)

    def parse_table_primary(self):
        if self.accept_op("("):
            # "((select" starts a parenthesized set-op chain, not a
            # parenthesized join
            if self.at_kw("select", "with") or self._at_paren_select():
                sub = self.parse_select()
                self.expect_op(")")
                self.accept_kw("as")
                alias = self.expect_ident()
                return ast.SubqueryRef(sub, alias)
            refs = self.parse_table_refs()
            self.expect_op(")")
            return refs
        if (self.peek().kind == "ident" and self.peek().value.lower() == "unnest"
                and self.peek(1).kind == "op" and self.peek(1).value == "("):
            self.next()
            self.expect_op("(")
            e = self.parse_expr()
            self.expect_op(")")
            self.accept_kw("as")
            alias = (self.next().value
                     if self.peek().kind == "ident" else "unnest")
            col = "unnest"
            if self.accept_op("("):
                col = self.expect_ident()
                self.expect_op(")")
            return ast.UnnestRef(e, alias, col)
        name = self.parse_table_name()
        alias = None
        if self.accept_kw("as"):
            alias = self.expect_ident()
        elif self.peek().kind == "ident":
            alias = self.next().value
        return ast.TableRef(name, alias)

    # --- expressions (precedence climbing) ------------------------------------
    def parse_expr(self) -> Expr:
        lam = self._try_parse_lambda()
        if lam is not None:
            return lam
        return self.parse_or()

    def _try_parse_lambda(self):
        """`x -> expr` / `(x, y) -> expr` (higher-order function arguments;
        reference: the lambda grammar of array_map/map_apply). Pure
        lookahead first, so ordinary expressions never backtrack."""
        if not getattr(self, "_call_depth", 0):
            return None  # not inside a function's argument list
        t = self.peek()
        if (t.kind == "ident" and self.peek(1).kind == "op"
                and self.peek(1).value == "->"):
            s = self.peek(2)
            if s.kind == "string" and s.value.startswith("$"):
                # `col -> '$.a'` is the JSON arrow operator, not a lambda
                # with a constant string body (parse_unary routes it to
                # get_json_string). Any other string rhs here is a lambda
                # body — `array_map(x -> 'abc', arr)` is valid HOF SQL
                return None
            name = self.next().value
            self.next()  # ->
            return ast.LambdaExpr((name,), self.parse_or())
        if t.kind == "op" and t.value == "(":
            j = 1
            names = []
            while True:
                tk = self.peek(j)
                if tk.kind != "ident":
                    return None
                names.append(tk.value)
                nxt = self.peek(j + 1)
                if nxt.kind == "op" and nxt.value == ",":
                    j += 2
                    continue
                if nxt.kind == "op" and nxt.value == ")":
                    j += 2
                    break
                return None
            arrow = self.peek(j)
            if not (arrow.kind == "op" and arrow.value == "->"):
                return None
            self.i += j + 1  # consume ( params ) ->
            return ast.LambdaExpr(tuple(names), self.parse_or())
        return None

    def parse_or(self) -> Expr:
        e = self.parse_and()
        while self.accept_kw("or"):
            e = Call("or", e, self.parse_and())
        return e

    def parse_and(self) -> Expr:
        e = self.parse_not()
        while self.accept_kw("and"):
            e = Call("and", e, self.parse_not())
        return e

    def parse_not(self) -> Expr:
        if self.accept_kw("not"):
            return Call("not", self.parse_not())
        return self.parse_predicate()

    def parse_predicate(self) -> Expr:
        e = self.parse_additive()
        while True:
            if self.at_op("=", "<>", "<", "<=", ">", ">="):
                op = self.next().value
                rhs = self.parse_additive()
                name = {"=": "eq", "<>": "ne", "<": "lt", "<=": "le",
                        ">": "gt", ">=": "ge"}[op]
                # ANY/ALL-less subquery comparison: = (select ...)
                e = Call(name, e, rhs)
                continue
            negated = False
            save = self.i
            if self.accept_kw("not"):
                negated = True
            if self.accept_kw("between"):
                lo = self.parse_additive()
                self.expect_kw("and")
                hi = self.parse_additive()
                between = Call("and", Call("ge", e, lo), Call("le", e, hi))
                e = Call("not", between) if negated else between
                continue
            if self.accept_kw("in"):
                self.expect_op("(")
                if self.at_kw("select", "with"):
                    sub = self.parse_select()
                    self.expect_op(")")
                    e = ast.InSubquery(e, sub, negated)
                else:
                    vals = [self.parse_literal_value()]
                    while self.accept_op(","):
                        vals.append(self.parse_literal_value())
                    self.expect_op(")")
                    e = InList(e, tuple(vals), negated)
                continue
            if self.accept_kw("like"):
                pat = self.parse_additive()
                e = Call("not_like" if negated else "like", e, pat)
                continue
            if negated:
                self.i = save
            if self.accept_kw("is"):
                neg = self.accept_kw("not")
                self.expect_kw("null")
                e = Call("is_not_null" if neg else "is_null", e)
                continue
            return e

    def parse_literal_value(self):
        """Value inside an IN list (python scalar)."""
        t = self.peek()
        if t.kind == "string":
            self.next()
            return t.value
        if t.kind == "number":
            self.next()
            return (_num_lit(t.value)
                    if "." in t.value or "e" in t.value.lower()
                    else int(t.value))
        if t.kind == "kw" and t.value == "null":
            self.next()
            return None
        if t.kind in ("kw", "ident") and t.value.lower() in (
                "date", "timestamp"):
            # typed literal: DATE 'YYYY-MM-DD' — the IN-list compiler coerces
            # plain ISO strings against the tested column's temporal type
            self.next()
            s = self.next()
            if s.kind != "string":
                raise ParseError(f"{t.value.upper()} literal expects a string")
            return s.value
        if t.kind == "op" and t.value == "-":
            self.next()
            v = self.parse_literal_value()
            return -v
        raise ParseError(f"expected literal in IN list at {t.value!r}")

    def parse_additive(self) -> Expr:
        e = self.parse_multiplicative()
        while True:
            if self.accept_op("+"):
                rhs = self.parse_multiplicative()
                e = self._plus_minus(e, rhs, "add")
            elif self.accept_op("-"):
                rhs = self.parse_multiplicative()
                e = self._plus_minus(e, rhs, "subtract")
            else:
                return e

    @staticmethod
    def _plus_minus(lhs, rhs, op):
        # date +/- INTERVAL folds into date_add_days/months
        if isinstance(rhs, Call) and rhs.fn == "__interval__":
            n, unit = rhs.args[0].value, rhs.args[1].value
            sign = 1 if op == "add" else -1
            if unit == "day":
                return Call("date_add_days", lhs, Lit(sign * n))
            if unit == "month":
                return Call("date_add_months", lhs, Lit(sign * n))
            if unit == "year":
                return Call("date_add_months", lhs, Lit(sign * 12 * n))
            raise ParseError(f"unsupported interval unit {unit}")
        return Call(op, lhs, rhs)

    def parse_multiplicative(self) -> Expr:
        e = self.parse_unary()
        while True:
            if self.accept_op("*"):
                e = Call("multiply", e, self.parse_unary())
            elif self.accept_op("/"):
                e = Call("divide", e, self.parse_unary())
            elif self.accept_op("%"):
                e = Call("mod", e, self.parse_unary())
            else:
                return e

    def parse_unary(self) -> Expr:
        if self.accept_op("-"):
            e = self.parse_unary()
            if isinstance(e, Lit) and isinstance(e.value, (int, float)):
                return Lit(-e.value, e.type)
            return Call("negate", e)
        if self.accept_op("+"):
            return self.parse_unary()
        e = self.parse_primary()
        # the JSON arrow operator: col -> '$.a' extracts a JSON path
        # (reference: StarRocks' json -> path = json_query). Lambdas also
        # use ->, but _try_parse_lambda (only active inside a call's
        # argument list) yields `ident ->` back here only for '$'-prefixed
        # path literals, so the two cannot collide; a non-string rhs here
        # is a clear error instead of a silent lambda.
        while self.at_op("->"):
            self.next()
            pt = self.next()
            if pt.kind != "string":
                raise ParseError(
                    "-> expects a JSON path string literal (lambdas are "
                    f"only valid as higher-order function arguments) at "
                    f"position {pt.pos}")
            e = Call("get_json_string", e, Lit(pt.value))
        return e

    def parse_primary(self) -> Expr:
        t = self.peek()
        if t.kind == "number":
            self.next()
            v = (_num_lit(t.value)
                 if "." in t.value or "e" in t.value.lower()
                 else int(t.value))
            return Lit(v)
        if t.kind == "string":
            self.next()
            return Lit(t.value)
        if t.kind == "kw":
            if t.value == "null":
                self.next()
                return Lit(None)
            if t.value in ("true", "false"):
                self.next()
                return Lit(t.value == "true")
            if t.value == "date" and self.peek(1).kind == "string":
                self.next()
                return Lit(self.next().value, T.DATE)
            if t.value == "interval":
                self.next()
                v = self.next()
                n = int(v.value)
                unit_t = self.next()
                unit = unit_t.value.rstrip("s") if unit_t.value else ""
                return Call("__interval__", Lit(n), Lit(unit))
            if t.value == "case":
                return self.parse_case()
            if t.value == "cast":
                self.next()
                self.expect_op("(")
                e = self.parse_expr()
                self.expect_kw("as")
                to = self.parse_type_name()
                self.expect_op(")")
                return Cast(e, to)
            if t.value == "extract":
                self.next()
                self.expect_op("(")
                unit = self.next().value
                self.expect_kw("from") if self.at_kw("from") else self.expect_ident()
                e = self.parse_expr()
                self.expect_op(")")
                if unit not in ("year", "month", "day"):
                    raise ParseError(f"EXTRACT({unit}) unsupported")
                return Call(unit, e)
            if t.value == "exists":
                self.next()
                self.expect_op("(")
                sub = self.parse_select()
                self.expect_op(")")
                return ast.Exists(sub)
            if t.value in ("year", "month", "day", "if", "substring", "left",
                           "right", "second", "replace", "values", "week"):
                # function-style keywords
                if self.peek(1).kind == "op" and self.peek(1).value == "(":
                    return self.parse_func_call(self.next().value)
        if t.kind == "op" and t.value == "(":
            self.next()
            if self.at_kw("select", "with"):
                sub = self.parse_select()
                self.expect_op(")")
                return ast.Subquery(sub)
            e = self.parse_expr()
            self.expect_op(")")
            return e
        if t.kind == "ident" or (
                t.kind == "kw" and t.value in self.SOFT_KEYWORDS):
            # a non-reserved word is a name wherever no syntax claims it:
            # `year(d) AS year ... GROUP BY year` (the call form of the
            # function-style ones was taken above, by the `(` after it)
            # func call / qualified col / bare col
            if self.peek(1).kind == "op" and self.peek(1).value == "(":
                e = self.parse_func_call(self.next().value)
                # postfix struct-field access: named_struct(...).a.b —
                # only after call forms, so t.c stays a qualified column
                while (self.at_op(".") and self.peek(1).kind == "ident"):
                    self.next()
                    e = Call("struct_field", e, Lit(self.expect_ident()))
                return e
            name = self.next().value
            if self.accept_op("."):
                col2 = self.expect_ident()
                return ast.RawCol(name, col2)
            return ast.RawCol(None, name)
        raise ParseError(f"unexpected token {t.value!r} (pos {t.pos})")

    # functions taking a leading bare unit keyword (MySQL style):
    # timestampdiff(DAY, a, b), date_trunc(month, x), extract-like forms
    _UNIT_ARG_FNS = {"timestampdiff", "timestampadd", "date_trunc",
                     "date_diff", "date_floor", "time_slice",
                     "date_slice"}
    _UNITS = {"year", "quarter", "month", "week", "day", "hour", "minute",
              "second", "millisecond"}

    def parse_func_call(self, name: str) -> Expr:
        name = name.lower()
        self.expect_op("(")
        distinct = self.accept_kw("distinct")
        # lambdas are only grammatical as function arguments (the
        # higher-order builtins); a bare `x -> expr` elsewhere is either
        # the JSON arrow (string rhs, parse_unary) or a clear error
        self._call_depth = getattr(self, "_call_depth", 0) + 1
        try:
            return self._parse_func_call_body(name, distinct)
        finally:
            self._call_depth -= 1

    def _parse_func_call_body(self, name: str, distinct: bool) -> Expr:
        args = []
        if (name in self._UNIT_ARG_FNS and self.peek().kind in ("kw", "ident")
                and self.peek().value.lower() in self._UNITS):
            args.append(Lit(self.next().value.lower()))
            self.expect_op(",")
        if self.at_op("*"):
            self.next()
            args = [ast.Star()]
        elif not self.at_op(")"):
            args.append(self.parse_expr())
            while self.accept_op(","):
                args.append(self.parse_expr())
        # group_concat tails: [ORDER BY e [ASC|DESC], ...] [SEPARATOR 's']
        gc_order, gc_sep = None, None
        if self.at_kw("order"):
            self.next()
            self.expect_kw("by")
            gc_order = []
            while True:
                e = self.parse_expr()
                asc = True
                if self.accept_kw("desc"):
                    asc = False
                else:
                    self.accept_kw("asc")
                gc_order.append((e, asc))
                if not self.accept_op(","):
                    break
        if (self.peek().kind == "ident"
                and self.peek().value.lower() == "separator"):
            self.next()
            t = self.next()
            if t.kind != "string":
                raise ParseError("SEPARATOR expects a string literal")
            gc_sep = t.value
        if (gc_order is not None or gc_sep is not None) \
                and name.lower() != "group_concat":
            raise ParseError(
                f"ORDER BY/SEPARATOR inside {name}() is not supported")
        self.expect_op(")")
        if self.at_kw("over"):
            return self.parse_over(name, args, distinct)
        name = AGG_ALIASES.get(name, name)
        if name in ("median", "approx_count_distinct", "ndv") and not args:
            raise ParseError(f"{name} takes one argument")
        if name == "median":
            return AggExpr("percentile_cont", args[0], distinct,
                           extra=(Lit(0.5),))
        if name == "ndv":
            # exact distinct count (zero-error; approx_count_distinct below
            # is the genuinely approximate HLL path at any scale)
            return AggExpr("count", args[0], True)
        if name == "hll_raw_agg":
            name = "hll_union"  # reference alias (returns the merged sketch)
        if name == "intersect_count":
            # intersect_count(bitmap_col, dim_col, v1, v2, ...): cardinality
            # of the AND of per-dim-value unions (be/src/exprs/agg/
            # intersect_count.h re-designed over dense planes)
            if len(args) < 3:
                raise ParseError(
                    "intersect_count takes (bitmap, dim, v1[, v2...])")
            return AggExpr("intersect_count", args[0], distinct,
                           extra=tuple(args[1:]))
        if name == "percentile_approx":
            # exact holistic percentile serves the approximate contract
            # (reference: be/src/exprs/agg/percentile_approx.h); optional
            # third compression argument is accepted and ignored
            if len(args) < 2:
                raise ParseError("percentile_approx takes (expr, fraction)")
            return AggExpr("percentile_cont", args[0], distinct,
                           extra=(args[1],))
        if name == "group_concat":
            # host-finalized aggregate (executor runs a side plan; see
            # runtime/executor.py _execute_group_concat). Separator comes
            # either as the legacy second argument or SEPARATOR 's';
            # ORDER BY items ride in extra as (expr, asc) tuples.
            if not args:
                raise ParseError("group_concat takes at least one argument")
            if len(args) > 1 and gc_sep is not None:
                raise ParseError(
                    "group_concat: use either a positional separator or "
                    "SEPARATOR, not both")
            sep = args[1] if len(args) > 1 else (
                Lit(gc_sep) if gc_sep is not None else Lit(","))
            return AggExpr("group_concat", args[0], distinct,
                           extra=(sep, *map(tuple, gc_order or ())))
        if name in AGG_FUNCS:
            if name == "count" and args and isinstance(args[0], ast.Star):
                return AggExpr("count", None, distinct)
            if name in AGG_EXTRA_ARG:
                if len(args) < 2:
                    raise ParseError(f"{name} takes two arguments")
                if name.startswith("percentile"):
                    frac = args[1]
                    if not (isinstance(frac, Lit)
                            and isinstance(frac.value, (int, float))
                            and 0.0 <= float(frac.value) <= 1.0):
                        raise ParseError(
                            f"{name} fraction must be a literal in [0, 1]")
                return AggExpr(name, args[0], distinct, extra=(args[1],))
            return AggExpr(name, args[0] if args else None, distinct)
        reg = SCALAR_FUNCS.get(name, name)
        if reg in _SCALAR_REGISTRY:
            return Call(reg, *args)
        return ast.RawFunc(name, tuple(args), distinct)

    WINDOW_ONLY = {"row_number", "rank", "dense_rank", "lead", "lag",
                   "first_value", "last_value", "ntile"}

    def parse_over(self, name, args, distinct):
        if distinct:
            raise ParseError("DISTINCT in window functions unsupported")
        if name not in AGG_FUNCS and name not in self.WINDOW_ONLY:
            raise ParseError(f"{name!r} is not a window function")
        self.expect_kw("over")
        self.expect_op("(")
        partition = []
        order = []
        if self.accept_kw("partition"):
            self.expect_kw("by")
            partition.append(self.parse_expr())
            while self.accept_op(","):
                partition.append(self.parse_expr())
        if self.accept_kw("order"):
            self.expect_kw("by")
            while True:
                o = self.parse_order_item()
                nf = o.nulls_first if o.nulls_first is not None else not o.asc
                order.append((o.expr, o.asc, nf))
                if not self.accept_op(","):
                    break
        frame = None
        if self.at_kw("rows", "range"):
            mode = "rows" if self.accept_kw("rows") else None
            if mode is None:
                self.expect_kw("range")
                mode = "range"

            def bound():
                if self.accept_kw("unbounded"):
                    if self.accept_kw("preceding"):
                        return ("up", None)
                    self.expect_kw("following")
                    return ("uf", None)
                if self.accept_kw("current"):
                    self.expect_kw("row")
                    return ("cr", None)
                v = self.parse_expr()
                if not (isinstance(v, Lit)
                        and isinstance(v.value, (int, float))
                        and not isinstance(v.value, bool)):
                    raise ParseError("frame offset must be a numeric literal")
                if v.value < 0:
                    raise ParseError("frame offset must be non-negative")
                if mode == "rows" and not isinstance(v.value, int):
                    raise ParseError("ROWS frame offset must be an integer")
                if self.accept_kw("preceding"):
                    return ("p", v.value)
                self.expect_kw("following")
                return ("f", v.value)

            if self.accept_kw("between"):
                s = bound()
                self.expect_kw("and")
                e = bound()
            else:
                s = bound()
                e = ("cr", None)
            rank = {"up": 0, "p": 1, "cr": 2, "f": 3, "uf": 4}
            if s[0] == "uf" or e[0] == "up" or rank[s[0]] > rank[e[0]]:
                raise ParseError(
                    f"invalid frame bounds ({s[0]} .. {e[0]})")
            if (s[0] == e[0] == "p" and s[1] < e[1]) or (
                    s[0] == e[0] == "f" and s[1] > e[1]):
                raise ParseError(
                    "frame start must not be after frame end")
            if not order:
                raise ParseError("a window frame requires ORDER BY")
            if (mode == "range"
                    and any(k in ("p", "f") for k in (s[0], e[0]))
                    and len(order) != 1):
                raise ParseError(
                    "RANGE with an offset requires exactly one ORDER BY key")
            if name in self.WINDOW_ONLY and name not in (
                    "first_value", "last_value"):
                raise ParseError(f"{name} does not accept a window frame")
            frame = (mode, s[0], s[1], e[0], e[1])
        self.expect_op(")")
        arg = None
        offset = 1
        default = None
        if args and not isinstance(args[0], ast.Star):
            arg = args[0]
        if name in ("lead", "lag"):
            if len(args) > 1:
                if not (isinstance(args[1], Lit) and isinstance(args[1].value, int)):
                    raise ParseError(f"{name} offset must be an integer literal")
                offset = args[1].value
            if len(args) > 2:
                if not isinstance(args[2], Lit):
                    raise ParseError(f"{name} default must be a literal")
                default = args[2].value
        elif name == "ntile":
            if not (isinstance(args[0], Lit) and isinstance(args[0].value, int)):
                raise ParseError("ntile requires an integer literal")
            offset = args[0].value
            arg = None
        return WindowExpr(name, arg, tuple(partition), tuple(order),
                          offset, default, frame)

    def parse_case(self) -> Expr:
        self.expect_kw("case")
        operand = None
        if not self.at_kw("when"):
            operand = self.parse_expr()
        whens = []
        while self.accept_kw("when"):
            c = self.parse_expr()
            self.expect_kw("then")
            v = self.parse_expr()
            if operand is not None:
                c = Call("eq", operand, c)
            whens.append((c, v))
        orelse = None
        if self.accept_kw("else"):
            orelse = self.parse_expr()
        self.expect_kw("end")
        return Case(tuple(whens), orelse)

    def parse_type_name(self) -> T.LogicalType:
        name = self.next().value.lower()
        if name == "array":
            # ARRAY<elem>
            self.expect_op("<")
            elem = self.parse_type_name()
            self.expect_op(">")
            return T.ARRAY(elem)
        if name in ("int", "integer"):
            return T.INT
        if name == "bigint":
            return T.BIGINT
        if name in ("smallint",):
            return T.SMALLINT
        if name in ("tinyint",):
            return T.TINYINT
        if name in ("float",):
            return T.FLOAT
        if name in ("double",):
            return T.DOUBLE
        if name in ("boolean", "bool"):
            return T.BOOLEAN
        if name in ("date",):
            return T.DATE
        if name in ("datetime", "timestamp"):
            return T.DATETIME
        if name in ("varchar", "char", "string", "text"):
            if self.accept_op("("):
                self.next()
                self.expect_op(")")
            return T.VARCHAR
        if name in ("decimal", "numeric"):
            p, s = 18, 0
            if self.accept_op("("):
                p = int(self.next().value)
                if self.accept_op(","):
                    s = int(self.next().value)
                self.expect_op(")")
            return T.DECIMAL(p, s)
        if name == "hll":
            p = 12
            if self.accept_op("("):
                p = int(self.next().value)
                self.expect_op(")")
            return T.HLL(p)
        if name == "bitmap":
            n = 65536
            if self.accept_op("("):
                n = int(self.next().value)
                self.expect_op(")")
            return T.BITMAP(n)
        raise ParseError(f"unknown type {name!r}")

    # --- DDL / DML -----------------------------------------------------------
    def _parse_user_name(self) -> str:
        t = self.next()
        if t.kind not in ("ident", "string"):
            raise ParseError(f"expected user name at {t.value!r}")
        return t.value

    def parse_create(self):
        self.expect_kw("create")
        replace = False
        if self.at_kw("or"):
            self.next()
            t = self.next()
            if t.value.lower() != "replace":
                raise ParseError("expected REPLACE after OR")
            replace = True
        if (self.peek().kind == "ident"
                and self.peek().value.lower() == "function"):
            # CREATE [OR REPLACE] FUNCTION f(a BIGINT, ...) RETURNS t AS 'py'
            self.next()
            name = self.expect_ident()
            self.expect_op("(")
            params = []
            if not self.at_op(")"):
                while True:
                    pname = self.expect_ident()
                    ptype = self.parse_type_name()
                    params.append((pname, ptype))
                    if not self.accept_op(","):
                        break
            self.expect_op(")")
            t = self.next()
            if t.value.lower() != "returns":
                raise ParseError("expected RETURNS")
            ret = self.parse_type_name()
            self.expect_kw("as")
            src = self.next()
            if src.kind != "string":
                raise ParseError("CREATE FUNCTION body must be a string")
            self.accept_op(";")
            return ast.CreateFunction(name, tuple(params), ret, src.value,
                                      replace)
        if (self.peek().kind == "ident"
                and self.peek().value.lower() == "resource"):
            # CREATE [OR REPLACE] RESOURCE GROUP name
            #   WITH (concurrency_limit = 2, max_scan_rows = 100000, ...)
            self.next()
            g = self.next()
            if g.value.lower() != "group":
                raise ParseError("expected GROUP after CREATE RESOURCE")
            name = self.expect_ident()
            props = []
            if self.accept_kw("with"):
                self.expect_op("(")
                while True:
                    pname = self.expect_ident().lower()
                    self.expect_op("=")
                    t = self.next()
                    if t.kind == "number":
                        val = int(t.value)
                    elif t.kind == "string":
                        val = int(t.value)
                    else:
                        raise ParseError(
                            "resource group property values are integers")
                    props.append((pname, val))
                    if not self.accept_op(","):
                        break
                self.expect_op(")")
            self.accept_op(";")
            return ast.CreateResourceGroup(name, tuple(props), replace)
        if replace:
            raise ParseError("OR REPLACE is only supported for FUNCTION")
        if (self.peek().kind == "ident"
                and self.peek().value.lower() == "external"):
            # CREATE EXTERNAL TABLE name FROM '<parquet dir/glob/file>'
            self.next()
            self.expect_kw("table")
            name = self.expect_ident()
            self.expect_kw("from")
            t = self.next()
            if t.kind != "string":
                raise ParseError(
                    "CREATE EXTERNAL TABLE expects a quoted location")
            self.accept_op(";")
            return ast.CreateExternalTable(name, t.value)
        if self.peek().kind == "ident" and self.peek().value.lower() == "user":
            self.next()
            user = self._parse_user_name()
            password = ""
            if (self.peek().kind == "ident"
                    and self.peek().value.lower() == "identified"):
                self.next()
                self.expect_kw("by")
                t = self.next()
                if t.kind != "string":
                    raise ParseError("IDENTIFIED BY expects a string")
                password = t.value
            self.accept_op(";")
            return ast.CreateUser(user, password)
        if self.at_kw("view", "materialized"):
            mat = self.accept_kw("materialized")
            self.expect_kw("view")
            name = self.expect_ident()
            self.expect_kw("as")
            start = self.peek().pos
            self.parse_select()  # validate syntax; body re-parsed on use
            end = self.peek().pos
            self.accept_op(";")
            # capture the raw text of the body for storage
            return ast.CreateView(name, self._sql_text[start:end or None], mat)
        self.expect_kw("table")
        name = self.expect_ident()
        if self.accept_kw("as"):
            sel = self.parse_select()
            self.accept_op(";")
            return ast.CreateTable(name, (), select=sel)
        self.expect_op("(")
        cols = []
        pk = ()
        while True:
            if self.at_kw("primary") and self.peek(1).kind == "kw" and self.peek(1).value == "key":
                self.next()
                self.expect_kw("key")
                self.expect_op("(")
                ks = [self.expect_ident()]
                while self.accept_op(","):
                    ks.append(self.expect_ident())
                self.expect_op(")")
                pk = tuple(ks)
                if not self.accept_op(","):
                    break
                continue
            cname = self.expect_ident()
            t = self.parse_type_name()
            nullable = True
            if self.accept_kw("not"):
                self.expect_kw("null")
                nullable = False
            else:
                self.accept_kw("null")
            cols.append(ast.ColumnDef(cname, t, nullable))
            if not self.accept_op(","):
                break
        self.expect_op(")")
        part = None
        if self.accept_kw("partition"):
            # PARTITION BY RANGE(col) (PARTITION p VALUES LESS THAN (lit|
            # MAXVALUE), ...) — fe catalog/RangePartitionInfo.java surface
            self.expect_kw("by")
            self.expect_kw("range")
            self.expect_op("(")
            pcol = self.expect_ident()
            self.expect_op(")")
            self.expect_op("(")
            pnames, uppers = [], []
            while True:
                self.expect_kw("partition")
                pnames.append(self.expect_ident())
                self.expect_kw("values")
                self.expect_kw("less")
                self.expect_kw("than")
                if self.accept_kw("maxvalue"):
                    uppers.append(None)
                else:
                    self.expect_op("(")
                    if self.accept_kw("maxvalue"):
                        uppers.append(None)
                    else:
                        lit = self.parse_expr()
                        if not isinstance(lit, Lit):
                            raise ParseError(
                                "partition bound must be a literal")
                        uppers.append(lit.value)
                    self.expect_op(")")
                if not self.accept_op(","):
                    break
            self.expect_op(")")
            for u1, u2 in zip(uppers, uppers[1:]):
                try:
                    bad = u1 is None or (u2 is not None and u2 <= u1)
                except TypeError:
                    raise ParseError(
                        "partition bounds must share one comparable type")
                if bad:
                    raise ParseError("partition bounds must be increasing")
            part = {"column": pcol, "names": pnames, "uppers": uppers}
        dist = ()
        buckets = 0
        if self.accept_kw("distributed"):
            self.expect_kw("by")
            self.expect_kw("hash")
            self.expect_op("(")
            d = [self.expect_ident()]
            while self.accept_op(","):
                d.append(self.expect_ident())
            self.expect_op(")")
            dist = tuple(d)
            if self.accept_kw("buckets"):
                buckets = int(self.next().value)
        self.accept_op(";")
        return ast.CreateTable(name, tuple(cols), dist, buckets,
                               primary_key=pk, partition_by=part)

    def parse_insert(self):
        self.expect_kw("insert")
        self.expect_kw("into")
        name = self.expect_ident()
        cols = ()
        if self.accept_op("("):
            c = [self.expect_ident()]
            while self.accept_op(","):
                c.append(self.expect_ident())
            self.expect_op(")")
            cols = tuple(c)
        if self.accept_kw("values"):
            rows = []
            while True:
                self.expect_op("(")
                row = [self.parse_expr()]
                while self.accept_op(","):
                    row.append(self.parse_expr())
                self.expect_op(")")
                rows.append(tuple(row))
                if not self.accept_op(","):
                    break
            self.accept_op(";")
            return ast.Insert(name, cols, None, tuple(rows))
        sel = self.parse_select()
        return ast.Insert(name, cols, sel, ())

    def parse_drop(self):
        self.expect_kw("drop")
        if self.peek().kind == "ident" and self.peek().value.lower() == "user":
            self.next()
            user = self._parse_user_name()
            self.accept_op(";")
            return ast.DropUser(user)
        if (self.peek().kind == "ident"
                and self.peek().value.lower() == "function"):
            self.next()
            if_exists = False
            if self.accept_kw("if"):
                self.expect_kw("exists")
                if_exists = True
            name = self.expect_ident()
            self.accept_op(";")
            return ast.DropFunction(name, if_exists)
        if (self.peek().kind == "ident"
                and self.peek().value.lower() == "resource"):
            self.next()
            g = self.next()
            if g.value.lower() != "group":
                raise ParseError("expected GROUP after DROP RESOURCE")
            if_exists = False
            if self.accept_kw("if"):
                self.expect_kw("exists")
                if_exists = True
            name = self.expect_ident()
            self.accept_op(";")
            return ast.DropResourceGroup(name, if_exists)
        self.expect_kw("table")
        if_exists = False
        if self.accept_kw("if"):
            self.expect_kw("exists")
            if_exists = True
        name = self.expect_ident()
        self.accept_op(";")
        return ast.DropTable(name, if_exists)


def parse(sql: str):
    p = Parser(sql)
    stmt = p.parse_statement()
    t = p.peek()
    if t.kind != "eof":
        raise ParseError(f"unexpected trailing input at {t.value!r} (pos {t.pos})")
    return stmt
