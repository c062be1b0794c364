"""Expression compiler: IR -> pure jax ops over a Chunk.

Reference behavior: be/src/exprs/ (76k LoC vectorized evaluators; function
registry generated from gensrc/script/functions.py:32). The TPU re-design
evaluates an Expr tree *at jit-trace time* into XLA ops, so the whole
expression (and the operator around it) fuses into one kernel.

Evaluation value: EVal(data, valid, type, dict)
- data: jnp array [capacity] (or 0-d scalar for literals, broadcast later)
- valid: bool array | None (None = never NULL)
- type: LogicalType
- dict: StringDict | None for VARCHAR values

NULL semantics: result NULL iff any input NULL (per-function override for
AND/OR Kleene logic, IS NULL, COALESCE, CASE). Null slots hold garbage that
must never be observed except through `valid`.

String strategy (TPU-first): dictionaries are trace-time constants, so
- comparisons against literals become integer code comparisons
  (sorted dicts make range predicates order-correct);
- IN lists and arbitrary string->bool functions (LIKE, regexp) become a
  constant boolean table over the dictionary, evaluated on the codes
  (`dict_code_mask`): compares against its runs of consecutive TRUE codes,
  or, for a set of many scattered codes, the table gathered per-row;
- string->string functions become constant remap tables into a new dict.
This is the reference's global low-cardinality dict rewrite
(be/src/compute_env/global_dict/parser.h) promoted to the only string path.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import datetime
import fnmatch
import functools
import re
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..column.column import Chunk
from ..column.dict_encoding import StringDict
from .ir import AggExpr, Call, Case, Cast, Col, Expr, InList, Lit
from .ir import Lambda as IrLambda


@dataclasses.dataclass
class EVal:
    data: jnp.ndarray
    valid: Optional[jnp.ndarray]
    type: T.LogicalType
    dict: Optional[StringDict] = None
    # static (lo, hi) value bounds known at trace time (from catalog stats),
    # propagated through a few closed-form functions; None = unbounded
    bounds: Optional[tuple] = None
    # the chunk column this value IS (a bare `Col`), for the `dict_predicates`
    # info; None for anything computed
    col: Optional[str] = None


def _and_valid(*valids):
    out = None
    for v in valids:
        if v is None:
            continue
        out = v if out is None else (out & v)
    return out


# --- literal handling -------------------------------------------------------


def _infer_lit(value, ltype: T.LogicalType | None) -> tuple:
    """Returns (host_value, LogicalType). Dates given as 'YYYY-MM-DD' strings
    with an explicit DATE type, or via date literal auto-detection."""
    if ltype is not None and ltype.kind is T.TypeKind.DATE and isinstance(value, str):
        d = datetime.date.fromisoformat(value)
        return (d - datetime.date(1970, 1, 1)).days, ltype
    if ltype is not None and ltype.kind is T.TypeKind.DATETIME and isinstance(value, str):
        dt = datetime.datetime.fromisoformat(value.replace(" ", "T"))
        us = (dt - datetime.datetime(1970, 1, 1)) // datetime.timedelta(microseconds=1)
        return us, ltype
    if value is None:
        # typed or not, a NULL literal is NULL; callers branch on value None
        return 0, T.NULLTYPE
    if isinstance(value, bool):
        return value, ltype or T.BOOLEAN
    if isinstance(value, int):
        if ltype is not None and ltype.is_decimal:
            return value * 10 ** ltype.scale, ltype
        if abs(value) >= (1 << 63):
            # beyond int64: the literal rides as DECIMAL128 limbs
            from ..column.host_table import _int_to_dec128

            return _int_to_dec128(value), T.DECIMAL(38, 0)
        return value, ltype or T.BIGINT
    if isinstance(value, float):
        if ltype is not None and ltype.is_decimal:
            return int(round(value * 10 ** ltype.scale)), ltype
        return value, ltype or T.DOUBLE
    import decimal

    if isinstance(value, decimal.Decimal):
        if ltype is not None and ltype.is_decimal:
            return int(value.scaleb(ltype.scale,
                                    decimal.Context(prec=60))), ltype
        exp = -value.as_tuple().exponent
        s = max(int(exp), 0)
        unscaled = int(value.scaleb(s, decimal.Context(prec=60)))
        if abs(unscaled) >= (1 << 63):
            # beyond int64/float64 exactness: carry the literal as
            # DECIMAL128 limbs so dec128 comparisons stay exact
            from ..column.host_table import _int_to_dec128

            return _int_to_dec128(unscaled), T.DECIMAL(38, s)
        return float(value), ltype or T.DOUBLE
    if isinstance(value, datetime.date):
        return (value - datetime.date(1970, 1, 1)).days, T.DATE
    if isinstance(value, str):
        # bare string literal; typed when it meets a dict column
        return value, ltype or T.VARCHAR
    raise TypeError(f"unsupported literal {value!r}")


_DATE_RE = re.compile(r"^\d{4}-\d{2}-\d{2}$")
_DATETIME_RE = re.compile(r"^\d{4}-\d{2}-\d{2}[ T]\d{2}:\d{2}(:\d{2}(\.\d+)?)?$")


def _lit_as_date_if_str(v: EVal) -> EVal:
    """Promote 'YYYY-MM-DD' / 'YYYY-MM-DD HH:MM[:SS]' string literals to
    DATE / DATETIME. Callers apply this only in TEMPORAL context (the other
    operand is a date/datetime) so ordinary string comparisons are untouched;
    unparseable look-alikes fall through unchanged."""
    if v.type.is_string and isinstance(v.data, str):
        if _DATE_RE.match(v.data):
            try:
                d = datetime.date.fromisoformat(v.data)
            except ValueError:
                return v
            days = (d - datetime.date(1970, 1, 1)).days
            return EVal(jnp.asarray(days, dtype=jnp.int32), v.valid, T.DATE)
        if _DATETIME_RE.match(v.data):
            try:
                dt = datetime.datetime.fromisoformat(v.data.replace(" ", "T"))
            except ValueError:
                return v
            us = (dt - datetime.datetime(1970, 1, 1)) // datetime.timedelta(
                microseconds=1
            )
            return EVal(jnp.asarray(us, dtype=jnp.int64), v.valid, T.DATETIME)
    if v.type.is_string and v.dict is not None:
        # dict-encoded VARCHAR column in temporal context: parse every
        # dictionary value once at trace time into a days/us LUT; rows whose
        # string doesn't parse become NULL (reference CAST semantics)
        days, us = [], []
        for s in v.dict.values:
            s = str(s).strip()
            d = None
            try:
                d = datetime.date.fromisoformat(s[:10])
            except ValueError:
                pass
            days.append(None if d is None else
                        (d - datetime.date(1970, 1, 1)).days)
            u = None
            if d is not None and len(s) > 10:
                try:
                    dt = datetime.datetime.fromisoformat(s.replace(" ", "T"))
                    u = ((dt - datetime.datetime(1970, 1, 1))
                         // datetime.timedelta(microseconds=1))
                except ValueError:
                    pass
            us.append(u)
        if not days:
            return v
        n = max(len(v.dict), 1)
        idx = jnp.clip(v.data, 0, n - 1)
        good = [d for d in days if d is not None]
        # parsed LUT values are trace-time constants: bounds come for free
        # (drives date_format and the dense-domain aggregation path)
        if any(u is not None for u in us):
            vals = [u if u is not None else
                    (d * 86_400_000_000 if d is not None else 0)
                    for u, d in zip(us, days)]
            okl = jnp.asarray(np.asarray(
                [d is not None for d in days], np.bool_))
            lut = jnp.asarray(np.asarray(vals, np.int64))
            gv = [x for x, d in zip(vals, days) if d is not None]
            b = (min(gv), max(gv)) if gv else None
            return EVal(lut[idx], _and_valid(v.valid, okl[idx]), T.DATETIME,
                        bounds=b)
        lut = jnp.asarray(np.asarray(
            [d if d is not None else 0 for d in days], np.int32))
        okl = jnp.asarray(np.asarray(
            [d is not None for d in days], np.bool_))
        b = (min(good), max(good)) if good else None
        return EVal(lut[idx], _and_valid(v.valid, okl[idx]), T.DATE,
                    bounds=b)
    return v


def _promote_temporal_literals(a: EVal, b: EVal):
    """Context coercion: string literals become dates/datetimes only when the
    OTHER operand is temporal (never hijack string-vs-string comparisons)."""
    if b.type.is_temporal:
        a = _lit_as_date_if_str(a)
    if a.type.is_temporal:
        b = _lit_as_date_if_str(b)
    return a, b


# --- numeric coercion -------------------------------------------------------


def _to_numeric(v: EVal, target: T.LogicalType) -> jnp.ndarray:
    """Cast v.data to target's representation (handles decimal rescale and
    temporal unit conversion)."""
    if v.type.is_decimal128 and target.is_float:
        from ..ops import dec128 as d128

        f = d128.to_f64(jnp.asarray(v.data)) / (10 ** v.type.scale)
        return jnp.asarray(f, target.dtype)
    if v.type.is_decimal128 and target.is_decimal128:
        if target.scale < v.type.scale:
            raise NotImplementedError("DECIMAL128 downscale cast")
        from ..ops import dec128 as d128

        return d128.rescale(jnp.asarray(v.data),
                            target.scale - v.type.scale)
    if target.is_decimal128:
        return _to_dec128(v, target.scale or 0)
    if v.type.kind is T.TypeKind.DATE and target.kind is T.TypeKind.DATETIME:
        return jnp.asarray(v.data, jnp.int64) * 86_400_000_000
    if v.type.kind is T.TypeKind.DATETIME and target.kind is T.TypeKind.DATE:
        return (jnp.asarray(v.data, jnp.int64) // 86_400_000_000).astype(jnp.int32)
    if v.type.is_decimal and target.is_decimal:
        d = jnp.asarray(v.data, dtype=jnp.int64)
        if v.type.scale < target.scale:
            d = d * (10 ** (target.scale - v.type.scale))
        elif v.type.scale > target.scale:
            d = d // (10 ** (v.type.scale - target.scale))
        return d
    if v.type.is_decimal and target.is_float:
        return jnp.asarray(v.data, dtype=target.dtype) / (10 ** v.type.scale)
    if (not v.type.is_decimal) and target.is_decimal:
        return jnp.asarray(v.data, dtype=jnp.int64) * (10 ** target.scale)
    return jnp.asarray(v.data, dtype=target.dtype)


def _common(a: EVal, b: EVal) -> T.LogicalType:
    if a.type.is_temporal or b.type.is_temporal:
        if a.type.kind == b.type.kind:
            return a.type
        if {a.type.kind, b.type.kind} == {T.TypeKind.DATE, T.TypeKind.DATETIME}:
            return T.DATETIME
        raise TypeError(f"cannot compare {a.type} and {b.type}")
    if a.type.is_string and b.type.is_string:
        return T.VARCHAR
    if a.type.kind is T.TypeKind.BOOLEAN and b.type.kind is T.TypeKind.BOOLEAN:
        return T.BOOLEAN
    return T.common_numeric_type(a.type, b.type)


# --- predicates over a dictionary column -------------------------------------

# A set of TRUE codes becomes compares on the codes while it has at most this
# many runs of consecutive codes, and a boolean table gathered a row above.
# `tools/dict_predicate_probe.py` on a v5e, 74,989,568 int32 codes (PERF.md
# section 6, PR 33): the table gathered a row is 700 ms (9.3 ns a row) at every
# dictionary length from 65 up; to 64 entries XLA's TPU compiler expands it into
# a chain of selects itself (1.2 ms at 5 entries, 2.4 at 64), no faster than
# the compares over the same dictionary. The compares read the column once:
# 1.2 ms to 8 runs, 3.8 at 64, 7.0 at 128, 17.2 at 256, 29.7 at 512, 54.6 at
# 1,024, so by time they win through the whole grid; what grows is the
# compile, 0.3 s to 64 runs, 0.7 at 128, 1.4 at 256, then 5.5 at 512 and
# 11.8 at 1,024. 256 is the last reading before that knee. The dictionary's
# length decides nothing: a set cannot have more runs than half of it.
RANGES_MAX_RUNS = 256

# where `dict_code_mask` records what it did: (sink, plan-node scope), set by
# the plan compilers around each node they emit (`dict_predicate_log`)
_DICT_PREDICATE_LOG: contextvars.ContextVar = contextvars.ContextVar(
    "dict_predicate_log", default=None)


@contextlib.contextmanager
def dict_predicate_log(sink: dict, scope: str):
    """While open, every `dict_code_mask` traced on this thread appends what
    it did to `sink[scope]`: a program's `dict_predicates` info."""
    token = _DICT_PREDICATE_LOG.set((sink, scope))
    try:
        yield
    finally:
        _DICT_PREDICATE_LOG.reset(token)


def dict_code_mask(codes, true: np.ndarray, column: str | None = None):
    """Row mask of a boolean predicate over a dictionary column: `true[code]`
    on every row whose code is in the dictionary (a NULL row carries any code
    and gets any bit; its validity is the caller's).

    `true` is the predicate over the dictionary, a trace-time constant. Split
    into its maximal runs of consecutive TRUE codes `[lo, hi]` it is `ranges`:
    the OR over the runs of `code == lo` or `(code >= lo) & (code <= hi)`,
    int32 compares XLA fuses into the one pass over the column (a sorted
    dictionary makes an IN list or a prefix LIKE a run or a few). A set of
    more than RANGES_MAX_RUNS runs stays `lut`: the table gathered a row."""
    codes = jnp.asarray(codes)
    true = np.asarray(true, np.bool_)
    edges = np.flatnonzero(np.diff(np.concatenate(
        [[False], true, [False]]).astype(np.int8)))
    runs = [(int(lo), int(hi) - 1) for lo, hi in zip(edges[::2], edges[1::2])]
    if len(runs) <= RANGES_MAX_RUNS:
        formulation = "ranges"
        parts = [(codes == lo) if lo == hi else ((codes >= lo) & (codes <= hi))
                 for lo, hi in runs]
        m = (functools.reduce(jnp.logical_or, parts) if parts
             else jnp.zeros(codes.shape, jnp.bool_))
    else:
        formulation = "lut"
        m = jnp.asarray(true)[jnp.clip(codes, 0, len(true) - 1)]
    log = _DICT_PREDICATE_LOG.get()
    if log is not None:
        sink, scope = log
        sink.setdefault(scope, []).append({
            "column": column, "dict": len(true),
            "true_codes": int(true.sum()), "runs": len(runs),
            "formulation": formulation})
    return m


# --- the compiler -----------------------------------------------------------


class ExprCompiler:
    """Compiles Expr trees against one Chunk. Stateless; cheap to construct."""

    def __init__(self, chunk: Chunk):
        self.chunk = chunk

    def eval(self, e: Expr) -> EVal:
        if isinstance(e, Col):
            data, valid = self.chunk.col(e.name)
            f = self.chunk.field(e.name)
            return EVal(data, valid, f.type, f.dict, bounds=f.bounds,
                        col=e.name)
        if isinstance(e, Lit):
            hv, lt = _infer_lit(e.value, e.type)
            if lt.kind is T.TypeKind.NULL:
                return EVal(
                    jnp.asarray(0, dtype=jnp.int32),
                    jnp.zeros((self.chunk.capacity,), dtype=jnp.bool_),
                    lt,
                )
            # literals stay as HOST scalars (strings and numbers alike):
            # jax 0.9 turns arrays constructed inside a jit trace into
            # tracers, which would break host consumers (substr bounds,
            # LIKE patterns); compute sites coerce via jnp.asarray where
            # needed and XLA constant-folds them
            return EVal(hv, None, lt)
        if isinstance(e, Cast):
            return self._cast(self.eval(e.arg), e.to)
        if isinstance(e, Case):
            return self._case(e)
        if isinstance(e, InList):
            return self._in_list(e)
        if isinstance(e, Call):
            fn = _FUNCTIONS.get(e.fn)
            if fn is None:
                from ..runtime.udf import eval_udf, get_udf

                udef = get_udf(e.fn)
                if udef is not None:
                    return eval_udf(self, udef,
                                    [self.eval(a) for a in e.args])
                raise KeyError(f"unknown function {e.fn!r}")
            # Lambda arguments stay UNevaluated: the higher-order builtin
            # compiles the body itself over the flattened lane view
            return fn(self, *[
                a if isinstance(a, IrLambda) else self.eval(a)
                for a in e.args
            ])
        if isinstance(e, EVal):
            return e  # pre-evaluated argument (cc.call composition)
        if isinstance(e, AggExpr):
            raise TypeError("aggregate expression in scalar context")
        raise TypeError(f"cannot evaluate {e!r}")

    def call(self, name: str, *vals):
        """Invoke a registered builtin on already-evaluated EVals (function
        composition: alias and derived builtins delegate through this)."""
        f = _FUNCTIONS.get(name)
        if f is None:
            raise KeyError(f"unknown function {name!r}")
        return f(self, *[v for v in vals if v is not None])

    def eval_predicate(self, e: Expr) -> jnp.ndarray:
        """Boolean mask for filters: NULL -> False (SQL WHERE semantics)."""
        v = self.eval(e)
        assert v.type.kind is T.TypeKind.BOOLEAN, f"predicate has type {v.type}"
        m = jnp.broadcast_to(jnp.asarray(v.data, dtype=jnp.bool_), (self.chunk.capacity,))
        if v.valid is not None:
            m = m & v.valid
        return m

    # --- casts --------------------------------------------------------------
    def _cast(self, v: EVal, to: T.LogicalType) -> EVal:
        if v.type == to:
            return v
        if v.type.is_string and not to.is_string:
            raise NotImplementedError("string->x casts not supported on device")
        if to.is_string:
            raise NotImplementedError("x->string casts not supported on device")
        # DATE<->DATETIME conversion is handled inside _to_numeric
        return EVal(_to_numeric(v, to), v.valid, to)

    # --- CASE ---------------------------------------------------------------
    def _case(self, e: Case) -> EVal:
        branches = [(self.eval(c), self.eval(v)) for c, v in e.whens]
        orelse = self.eval(e.orelse) if e.orelse is not None else None
        # result type = common type of all branch values
        vals = [bv for _, bv in branches] + ([orelse] if orelse else [])
        out_t = vals[0].type
        for v in vals[1:]:
            out_t = _common_valued(out_t, v.type)
        cap = self.chunk.capacity
        if orelse is not None:
            acc = jnp.broadcast_to(_to_numeric(orelse, out_t), (cap,))
            acc_valid = (
                jnp.ones((cap,), jnp.bool_) if orelse.valid is None else orelse.valid
            )
        else:
            acc = jnp.zeros((cap,), out_t.dtype)
            acc_valid = jnp.zeros((cap,), jnp.bool_)
        # apply WHENs last-to-first so the first true condition wins
        for cond, val in reversed(branches):
            c = jnp.broadcast_to(jnp.asarray(cond.data, jnp.bool_), (cap,))
            if cond.valid is not None:
                c = c & cond.valid
            d = jnp.broadcast_to(_to_numeric(val, out_t), (cap,))
            acc = jnp.where(c, d, acc)
            bv = (
                jnp.ones((cap,), jnp.bool_)
                if val.valid is None
                else jnp.broadcast_to(val.valid, (cap,))
            )
            acc_valid = jnp.where(c, bv, acc_valid)
        return EVal(acc, acc_valid, out_t)

    # --- IN list ------------------------------------------------------------
    def _in_list(self, e: InList) -> EVal:
        v = self.eval(e.arg)
        cap = self.chunk.capacity
        has_null = any(x is None for x in e.values)
        values = [x for x in e.values if x is not None]
        if v.type.is_decimal128:
            # OR of exact limb equalities (the 128-bit compare kernels)
            import decimal as _d

            from ..column.host_table import _int_to_dec128
            from ..ops import dec128 as d128

            ctx = _d.Context(prec=60)
            m = jnp.zeros((cap,), jnp.bool_)
            for x in values:
                scaled = _d.Decimal(str(x)).scaleb(v.type.scale, ctx)
                if scaled != scaled.to_integral_value(_d.ROUND_FLOOR, ctx):
                    continue  # inexact at this scale: can never match
                m = m | d128.eq(v.data,
                                jnp.asarray(_int_to_dec128(int(scaled))))
        elif v.type.is_string:
            codes = {v.dict.encode_one(str(x)) for x in values}
            codes.discard(-1)
            true = np.zeros((len(v.dict),), dtype=np.bool_)
            true[list(codes)] = True
            m = dict_code_mask(v.data, true, v.col)
        else:
            m = jnp.zeros((cap,), jnp.bool_)
            for x in values:
                hv, lt = _infer_lit(x, v.type if not v.type.is_float else None)
                m = m | (
                    jnp.broadcast_to(v.data, (cap,))
                    == jnp.asarray(hv, dtype=v.type.dtype)
                )
        # SQL: 'x IN (a, NULL)' is TRUE on match, NULL otherwise (never FALSE);
        # NOT IN flips the value, validity is unchanged.
        valid = v.valid
        if has_null:
            valid = m if valid is None else (valid & m)
        return EVal(~m if e.negated else m, valid, T.BOOLEAN)


def _common_valued(a: T.LogicalType, b: T.LogicalType) -> T.LogicalType:
    if a.kind is T.TypeKind.NULL:
        return b
    if b.kind is T.TypeKind.NULL:
        return a
    if a == b:
        return a
    return T.common_numeric_type(a, b)


# --- function registry ------------------------------------------------------

_FUNCTIONS = {}


def function(name, scope=None):
    """Registers a builtin. With `scope`, its operations carry the name
    `<scope>/<name>` under their plan node's (`sr.project.2/datepart/year`),
    so a device trace says what of a filter, projection or aggregate is that
    builtin's arithmetic; `f` itself stays callable without it."""

    def deco(f):
        if scope is None:
            _FUNCTIONS[name] = f
            return f

        @functools.wraps(f)
        def scoped(cc, *args):
            with jax.named_scope(f"{scope}/{name}"):
                return f(cc, *args)

        _FUNCTIONS[name] = scoped
        return f

    return deco


# calendar fields of a date: civil-from-days arithmetic, a row at a time
DATE_PART = "datepart"


def _binary_numeric(cc: ExprCompiler, a: EVal, b: EVal, op, scale_rule):
    a, b = _promote_temporal_literals(a, b)
    ct = _common(a, b)
    if ct.is_decimal:
        ct = scale_rule(a, b, ct)
    da, db = _to_numeric(a, ct), _to_numeric(b, ct)
    return op(da, db), _and_valid(a.valid, b.valid), ct, a, b


def _scale_maxpad(a, b, ct):
    return ct


def _is_dec128_pair(a, b):
    nonfloat = all(t.is_decimal or t.is_decimal128 or t.is_integer
                   or t.kind is T.TypeKind.BOOLEAN for t in (a.type, b.type))
    return nonfloat and (a.type.is_decimal128 or b.type.is_decimal128)


def _dec128_addsub(a: EVal, b: EVal, is_sub: bool) -> EVal:
    from ..ops import dec128 as d128

    sa = a.type.scale if (a.type.is_decimal or a.type.is_decimal128) else 0
    sb = b.type.scale if (b.type.is_decimal or b.type.is_decimal128) else 0
    s = max(sa, sb)
    da, db = _to_dec128(a, s), _to_dec128(b, s)
    out = d128.sub(da, db) if is_sub else d128.add(da, db)
    return EVal(out, _and_valid(a.valid, b.valid), T.DECIMAL(38, s))


@function("add")
def _f_add(cc, a, b):
    if _is_dec128_pair(a, b):
        return _dec128_addsub(a, b, False)
    d, v, t, *_ = _binary_numeric(cc, a, b, jnp.add, _scale_maxpad)
    return EVal(d, v, t)


@function("subtract")
def _f_sub(cc, a, b):
    if _is_dec128_pair(a, b):
        return _dec128_addsub(a, b, True)
    d, v, t, *_ = _binary_numeric(cc, a, b, jnp.subtract, _scale_maxpad)
    return EVal(d, v, t)


def _dec128_mul(a: EVal, b: EVal) -> EVal:
    from ..ops import dec128 as d128

    sa = a.type.scale if (a.type.is_decimal or a.type.is_decimal128) else 0
    sb = b.type.scale if (b.type.is_decimal or b.type.is_decimal128) else 0
    if sa + sb > 38:
        raise NotImplementedError(f"decimal multiply scale {sa + sb} > 38")
    out = d128.mul(_to_dec128(a, sa), _to_dec128(b, sb))
    return EVal(out, _and_valid(a.valid, b.valid), T.DECIMAL(38, sa + sb))


@function("multiply")
def _f_mul(cc, a, b):
    a, b = _promote_temporal_literals(a, b)
    if _is_dec128_pair(a, b):
        return _dec128_mul(a, b)
    ct = _common(a, b)
    if ct.is_decimal:
        sa = a.type.scale if a.type.is_decimal else 0
        sb = b.type.scale if b.type.is_decimal else 0
        out_s = sa + sb
        if out_s > 18:
            # product scale overflows DECIMAL64: promote to the 128-bit path
            return _dec128_mul(a, b)
        da = jnp.asarray(a.data, jnp.int64) if a.type.is_decimal else _to_numeric(a, T.DECIMAL(18, 0))
        db = jnp.asarray(b.data, jnp.int64) if b.type.is_decimal else _to_numeric(b, T.DECIMAL(18, 0))
        return EVal(da * db, _and_valid(a.valid, b.valid), T.DECIMAL(18, out_s))
    da, db = _to_numeric(a, ct), _to_numeric(b, ct)
    return EVal(da * db, _and_valid(a.valid, b.valid), ct)


@function("divide")
def _f_div(cc, a, b):
    # SQL semantics: x/0 -> NULL. Result computed in DOUBLE.
    da = _to_numeric(a, T.DOUBLE)
    db = _to_numeric(b, T.DOUBLE)
    zero = db == 0.0
    d = da / jnp.where(zero, 1.0, db)
    v = _and_valid(a.valid, b.valid, ~zero)
    return EVal(d, v, T.DOUBLE)


@function("mod")
def _f_mod(cc, a, b):
    # SQL MOD: truncated remainder (sign of the dividend), x % 0 -> NULL
    ct = _common(a, b)
    da, db = _to_numeric(a, ct), _to_numeric(b, ct)
    zero = db == 0
    safe_db = jnp.where(zero, jnp.ones_like(db), db)
    mag = jnp.abs(da) % jnp.abs(safe_db)
    d = jnp.where(da < 0, -mag, mag)
    return EVal(d, _and_valid(a.valid, b.valid, ~zero), ct)


@function("negate")
def _f_neg(cc, a):
    return EVal(-jnp.asarray(a.data), a.valid, a.type)


@function("abs")
def _f_abs(cc, a):
    return EVal(jnp.abs(jnp.asarray(a.data)), a.valid, a.type)


def _dec128_guard(*vals):
    for v in vals:
        if v.type.is_array:
            raise NotImplementedError(
                f"comparisons over {v.type} are not supported yet "
                "(compare via array functions)")


def _to_dec128(v: EVal, scale: int):
    """v's data as [cap, 4] limbs at `scale` (exact widening casts only)."""
    from ..ops import dec128 as d128

    if v.type.is_decimal128:
        if v.type.scale > scale:
            raise NotImplementedError("DECIMAL128 downscale in comparison")
        return d128.rescale(jnp.asarray(v.data), scale - v.type.scale)
    if v.type.is_decimal:
        d = d128.from_i64(jnp.asarray(v.data, jnp.int64))
        return d128.rescale(d, scale - v.type.scale)
    if v.type.is_integer or v.type.kind is T.TypeKind.BOOLEAN:
        return d128.rescale(
            d128.from_i64(jnp.asarray(v.data, jnp.int64)), scale)
    if v.type.is_float and np.ndim(v.data) == 0 \
            and not isinstance(v.data, jnp.ndarray):
        # concrete float literal: exact iff it round-trips at this scale
        # (decimal literals small enough for float64 always do)
        iv = int(round(float(v.data) * (10 ** scale)))
        if iv / (10 ** scale) == float(v.data) and abs(iv) < (1 << 63):
            return d128.from_i64(jnp.asarray(iv, jnp.int64))
    raise NotImplementedError(
        f"cannot widen {v.type!r} to DECIMAL128 exactly (cast to DOUBLE)")


def _compare_dec128(cc, a: EVal, b: EVal, op):
    from ..ops import dec128 as d128

    sa = a.type.scale if (a.type.is_decimal or a.type.is_decimal128) else 0
    sb = b.type.scale if (b.type.is_decimal or b.type.is_decimal128) else 0
    s = max(sa, sb)
    da, db = _to_dec128(a, s), _to_dec128(b, s)
    if op is jnp.equal:
        res = d128.eq(da, db)
    elif op is jnp.not_equal:
        res = ~d128.eq(da, db)
    elif op is jnp.less:
        res = d128.lt(da, db)
    elif op is jnp.less_equal:
        res = ~d128.lt(db, da)
    elif op is jnp.greater:
        res = d128.lt(db, da)
    else:  # greater_equal
        res = ~d128.lt(da, db)
    return EVal(res, _and_valid(a.valid, b.valid), T.BOOLEAN)


def _exact_decimal_literal(lit: EVal, col_type) -> EVal | None:
    """A concrete float literal as an exact DECIMAL literal for comparison
    with a `col_type` (DECIMAL64) column, or None when it has no exact
    decimal form the column can be widened to inside 18 digits. Comparing as
    scaled integers is exact on every backend; the alternative casts the
    COLUMN to DOUBLE by a float64 division, which a TPU emulates without
    correct rounding (on a v5e 5/100.0 < 0.05, so TPC-H Q6's `l_discount
    between 0.05 and 0.07` silently lost every 0.05 row)."""
    if not (lit.type.is_float and np.ndim(lit.data) == 0
            and not isinstance(lit.data, jnp.ndarray)):
        return None
    x = float(lit.data)
    for s in range(col_type.scale,
                   col_type.scale + 18 - col_type.precision + 1):
        iv = int(round(x * 10 ** s))
        if iv / 10 ** s == x and abs(iv) < 10 ** 18:
            return EVal(iv, None, T.DECIMAL(18, s))
    return None


def _compare(cc, a, b, op):
    _dec128_guard(a, b)
    if a.type.is_decimal128 or b.type.is_decimal128:
        return _compare_dec128(cc, a, b, op)
    a, b = _promote_temporal_literals(a, b)
    if a.type.is_string or b.type.is_string:
        return _compare_strings(cc, a, b, op)
    if a.type.is_decimal and b.type.is_float:
        b = _exact_decimal_literal(b, a.type) or b
    elif b.type.is_decimal and a.type.is_float:
        a = _exact_decimal_literal(a, b.type) or a
    ct = _common(a, b)
    if ct.is_decimal:
        # compare at the max scale of both sides
        sa = a.type.scale if a.type.is_decimal else 0
        sb = b.type.scale if b.type.is_decimal else 0
        ct = T.DECIMAL(18, max(sa, sb))
    da, db = _to_numeric(a, ct), _to_numeric(b, ct)
    return EVal(op(da, db), _and_valid(a.valid, b.valid), T.BOOLEAN)


def _compare_strings(cc, a: EVal, b: EVal, op):
    # column vs literal: compare codes against the literal's rank in the dict
    if a.dict is not None and isinstance(b.data, str):
        d = a.dict
        s = b.data
        if op in (jnp.equal, jnp.not_equal):
            code = d.encode_one(s)
            if code < 0:
                base = jnp.zeros_like(jnp.asarray(a.data), dtype=jnp.bool_)
                res = base if op is jnp.equal else ~base
            else:
                res = op(a.data, jnp.asarray(code, jnp.int32))
            return EVal(res, a.valid, T.BOOLEAN)
        # order comparison: sorted dict => rank position is correct
        pos = int(np.searchsorted(d.values.astype(str), s))
        exists = pos < len(d) and str(d.values[pos]) == s
        code = pos  # insertion point (== rank whether or not s exists)
        if op is jnp.less:
            res = jnp.asarray(a.data) < code
        elif op is jnp.less_equal:
            res = jnp.asarray(a.data) < (code + 1 if exists else code)
        elif op is jnp.greater:
            res = jnp.asarray(a.data) >= (code + 1 if exists else code)
        elif op is jnp.greater_equal:
            res = jnp.asarray(a.data) >= code
        else:
            raise AssertionError
        return EVal(res, a.valid, T.BOOLEAN)
    if b.dict is not None and isinstance(a.data, str):
        flipped = {
            jnp.equal: jnp.equal,
            jnp.not_equal: jnp.not_equal,
            jnp.less: jnp.greater,
            jnp.less_equal: jnp.greater_equal,
            jnp.greater: jnp.less,
            jnp.greater_equal: jnp.less_equal,
        }[op]
        return _compare_strings(cc, b, a, flipped)
    if a.dict is not None and b.dict is not None:
        if a.dict is b.dict:
            return EVal(op(a.data, b.data), _and_valid(a.valid, b.valid), T.BOOLEAN)
        # remap b's codes into a's dict ordering via merged dict
        m, ra, rb = a.dict.merge(b.dict)
        ra_t = jnp.asarray(ra)
        rb_t = jnp.asarray(rb)
        da = ra_t[jnp.clip(a.data, 0, len(ra) - 1)]
        db = rb_t[jnp.clip(b.data, 0, len(rb) - 1)]
        return EVal(op(da, db), _and_valid(a.valid, b.valid), T.BOOLEAN)
    if isinstance(a.data, str) and isinstance(b.data, str):
        # literal vs literal: rank both in a shared 2-entry dict
        m, _ = StringDict.from_strings([a.data, b.data])
        ra, rb = m.encode([a.data])[0], m.encode([b.data])[0]
        return EVal(op(jnp.asarray(ra), jnp.asarray(rb)),
                    _and_valid(a.valid, b.valid), T.BOOLEAN)
    raise NotImplementedError("string comparison without dictionaries")


@function("eq")
def _f_eq(cc, a, b):
    return _compare(cc, a, b, jnp.equal)


@function("ne")
def _f_ne(cc, a, b):
    return _compare(cc, a, b, jnp.not_equal)


@function("lt")
def _f_lt(cc, a, b):
    return _compare(cc, a, b, jnp.less)


@function("le")
def _f_le(cc, a, b):
    return _compare(cc, a, b, jnp.less_equal)


@function("gt")
def _f_gt(cc, a, b):
    return _compare(cc, a, b, jnp.greater)


@function("ge")
def _f_ge(cc, a, b):
    return _compare(cc, a, b, jnp.greater_equal)


@function("and")
def _f_and(cc, a, b):
    # Kleene: F & NULL = F, T & NULL = NULL
    da = jnp.asarray(a.data, jnp.bool_)
    db = jnp.asarray(b.data, jnp.bool_)
    va = a.valid if a.valid is not None else None
    vb = b.valid if b.valid is not None else None
    res = da & db
    if va is None and vb is None:
        return EVal(res, None, T.BOOLEAN)
    ta = da if va is None else (da & va)  # definitely true
    fa = ~da if va is None else (~da & va)  # definitely false
    tb = db if vb is None else (db & vb)
    fb = ~db if vb is None else (~db & vb)
    valid = fa | fb | (ta & tb)
    return EVal(ta & tb, valid, T.BOOLEAN)


@function("or")
def _f_or(cc, a, b):
    da = jnp.asarray(a.data, jnp.bool_)
    db = jnp.asarray(b.data, jnp.bool_)
    va, vb = a.valid, b.valid
    if va is None and vb is None:
        return EVal(da | db, None, T.BOOLEAN)
    ta = da if va is None else (da & va)
    fa = ~da if va is None else (~da & va)
    tb = db if vb is None else (db & vb)
    fb = ~db if vb is None else (~db & vb)
    valid = ta | tb | (fa & fb)
    return EVal(ta | tb, valid, T.BOOLEAN)


@function("not")
def _f_not(cc, a):
    return EVal(~jnp.asarray(a.data, jnp.bool_), a.valid, T.BOOLEAN)


@function("is_null")
def _f_is_null(cc, a):
    cap = cc.chunk.capacity
    if a.valid is None:
        return EVal(jnp.zeros((cap,), jnp.bool_), None, T.BOOLEAN)
    return EVal(~jnp.broadcast_to(a.valid, (cap,)), None, T.BOOLEAN)


@function("is_not_null")
def _f_is_not_null(cc, a):
    cap = cc.chunk.capacity
    if a.valid is None:
        return EVal(jnp.ones((cap,), jnp.bool_), None, T.BOOLEAN)
    return EVal(jnp.broadcast_to(a.valid, (cap,)), None, T.BOOLEAN)


@function("null_of")
def _f_null_of(cc, a):
    # typed NULL column shaped like `a` (ROLLUP's grouping placeholder)
    cap = cc.chunk.capacity
    data = jnp.broadcast_to(jnp.asarray(a.data), (cap,)) if not isinstance(a.data, (str, int, float, bool)) else jnp.zeros((cap,), a.type.dtype)
    return EVal(data, jnp.zeros((cap,), jnp.bool_), a.type, a.dict)


@function("coalesce")
def _f_coalesce(cc, *args):
    out = args[-1]
    for v in reversed(args[:-1]):
        if v.valid is None:
            out = v
            continue
        ct = _common_valued(v.type, out.type)
        dv = jnp.broadcast_to(_to_numeric(v, ct), (cc.chunk.capacity,))
        do = jnp.broadcast_to(_to_numeric(out, ct), (cc.chunk.capacity,))
        ov = (
            jnp.ones((cc.chunk.capacity,), jnp.bool_)
            if out.valid is None
            else out.valid
        )
        out = EVal(jnp.where(v.valid, dv, do), v.valid | ov, ct)
    return out


@function("if")
def _f_if(cc, c, a, b):
    ct = _common_valued(a.type, b.type)
    cap = cc.chunk.capacity
    cond = jnp.broadcast_to(jnp.asarray(c.data, jnp.bool_), (cap,))
    if c.valid is not None:
        cond = cond & c.valid
    da = jnp.broadcast_to(_to_numeric(a, ct), (cap,))
    db = jnp.broadcast_to(_to_numeric(b, ct), (cap,))
    d = jnp.where(cond, da, db)
    va = jnp.ones((cap,), jnp.bool_) if a.valid is None else a.valid
    vb = jnp.ones((cap,), jnp.bool_) if b.valid is None else b.valid
    v = jnp.where(cond, va, vb)
    if a.valid is None and b.valid is None:
        v = None
    return EVal(d, v, ct)


# --- dates ------------------------------------------------------------------
# civil-from-days (Howard Hinnant's algorithm), vectorized over int32 days.


def _civil_from_days(days, dtype=jnp.int64):
    """(year, month, day) of days since 1970. `dtype` int32 is exact for
    every DATE (the days of years 0..9999 shifted to 0000-03-01 stay under
    2^22 and every product below under 2^25) and is what the date parts of
    a column pass: in int64 a TPU emulates each of the dozen divisions
    (weekofyear over 75M rows: 374 ms on a v5e and 581 s of XLA compile,
    PR 32). The default keeps the other callers' programs as they were."""
    z = jnp.asarray(days, dtype) + 719_468
    era = jnp.where(z >= 0, z, z - 146_096) // 146_097
    doe = z - era * 146_097
    yoe = (doe - doe // 1460 + doe // 36_524 - doe // 146_096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    y = jnp.where(m <= 2, y + 1, y)
    return y.astype(jnp.int32), m.astype(jnp.int32), d.astype(jnp.int32)


def _as_days(v: EVal):
    if v.type.kind is T.TypeKind.DATE:
        return v.data
    if v.type.kind is T.TypeKind.DATETIME:
        return (jnp.asarray(v.data) // 86_400_000_000).astype(jnp.int32)
    raise TypeError(f"expected date/datetime, got {v.type}")


def _py_year_of_days(days: int) -> int:
    """Host-side civil year of a days-since-epoch value (bounds math)."""
    import datetime

    return (datetime.date(1970, 1, 1)
            + datetime.timedelta(days=int(days))).year


def _date_bounds_days(a: EVal):
    """arg bounds as days-since-epoch, or None."""
    if a.bounds is None:
        return None
    lo, hi = a.bounds
    if a.type.kind is T.TypeKind.DATETIME:
        return (int(lo) // 86_400_000_000, int(hi) // 86_400_000_000)
    if a.type.kind is T.TypeKind.DATE:
        return (int(lo), int(hi))
    return None


@function("year", scope=DATE_PART)
def _f_year(cc, a):
    a = _lit_as_date_if_str(a)
    y, m, d = _civil_from_days(_as_days(a), jnp.int32)
    db = _date_bounds_days(a)
    yb = ((_py_year_of_days(db[0]), _py_year_of_days(db[1]))
          if db is not None else None)
    return EVal(y, a.valid, T.INT, bounds=yb)


@function("month", scope=DATE_PART)
def _f_month(cc, a):
    a = _lit_as_date_if_str(a)
    y, m, d = _civil_from_days(_as_days(a), jnp.int32)
    return EVal(m, a.valid, T.INT, bounds=(1, 12))


@function("day", scope=DATE_PART)
def _f_day(cc, a):
    a = _lit_as_date_if_str(a)
    y, m, d = _civil_from_days(_as_days(a), jnp.int32)
    return EVal(d, a.valid, T.INT, bounds=(1, 31))


@function("date_add_days")
def _f_date_add_days(cc, a, n):
    a = _lit_as_date_if_str(a)
    return EVal(
        jnp.asarray(a.data, jnp.int32) + jnp.asarray(n.data, jnp.int32),
        _and_valid(a.valid, n.valid),
        T.DATE,
    )


def _days_from_civil(y, m, d, dtype=jnp.int64):
    yy = jnp.asarray(y, dtype) - jnp.asarray(m <= 2, dtype)
    era = jnp.where(yy >= 0, yy, yy - 399) // 400
    yoe = yy - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return (era * 146_097 + doe - 719_468).astype(jnp.int32)


@function("date_add_months")
def _f_date_add_months(cc, a, n):
    a = _lit_as_date_if_str(a)
    y, m, d = _civil_from_days(_as_days(a))
    months = jnp.asarray(y, jnp.int64) * 12 + (m - 1) + jnp.asarray(n.data, jnp.int64)
    y2 = months // 12
    m2 = (months % 12 + 1).astype(jnp.int64)
    leap = ((y2 % 4 == 0) & ((y2 % 100 != 0) | (y2 % 400 == 0))).astype(jnp.int64)
    dim = jnp.asarray([31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31], jnp.int64)[
        m2 - 1
    ] + jnp.where(m2 == 2, leap, 0)
    d2 = jnp.minimum(jnp.asarray(d, jnp.int64), dim)
    return EVal(
        _days_from_civil(y2, m2, d2), _and_valid(a.valid, n.valid), T.DATE
    )


# --- strings (dict LUT machinery) -------------------------------------------


def _string_bool_fn(cc, a: EVal, pred) -> EVal:
    if a.dict is None and isinstance(a.data, str):
        return EVal(jnp.asarray(bool(pred(a.data))), a.valid, T.BOOLEAN)
    assert a.dict is not None, "string function needs a dict column"
    return EVal(dict_code_mask(a.data, a.dict.lut(pred), a.col), a.valid,
                T.BOOLEAN)


def like_to_regex(pattern: str) -> str:
    out = []
    i = 0
    while i < len(pattern):
        ch = pattern[i]
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        elif ch == "\\" and i + 1 < len(pattern):
            out.append(re.escape(pattern[i + 1]))
            i += 1
        else:
            out.append(re.escape(ch))
        i += 1
    return "^" + "".join(out) + "$"


@function("like")
def _f_like(cc, a, pat):
    assert isinstance(pat.data, str), "LIKE pattern must be a literal"
    rx = re.compile(like_to_regex(pat.data), re.S)
    return _string_bool_fn(cc, a, lambda s: rx.match(str(s)) is not None)


@function("not_like")
def _f_not_like(cc, a, pat):
    v = _f_like(cc, a, pat)
    return EVal(~v.data, v.valid, T.BOOLEAN)


@function("starts_with")
def _f_starts_with(cc, a, pre):
    p = str(pre.data)
    return _string_bool_fn(cc, a, lambda s: str(s).startswith(p))


def _string_map_fn(cc, a: EVal, f) -> EVal:
    """string->string function via constant remap into a fresh dict."""
    if a.dict is None and isinstance(a.data, str):
        return EVal(str(f(a.data)), a.valid, T.VARCHAR)
    assert a.dict is not None
    mapped = [str(f(str(s))) for s in a.dict.values]
    new_dict, codes = StringDict.from_strings(mapped) if mapped else (
        StringDict.from_values([]),
        np.zeros(0, np.int32),
    )
    remap = jnp.asarray(codes) if len(codes) else jnp.zeros((1,), jnp.int32)
    n = max(len(a.dict), 1)
    out = remap[jnp.clip(a.data, 0, n - 1)]
    return EVal(out, a.valid, T.VARCHAR, new_dict)


@function("upper")
def _f_upper(cc, a):
    return _string_map_fn(cc, a, str.upper)


@function("lower")
def _f_lower(cc, a):
    return _string_map_fn(cc, a, str.lower)


@function("substr")
def _f_substr(cc, a, start, length=None):
    st = int(start.data)
    ln = None if length is None else int(length.data)

    def sub(s: str) -> str:
        # SQL semantics: 1-based; negative start counts from the end;
        # start 0 or |start| > len(s) yields ''
        if st == 0:
            return ""
        idx = st - 1 if st > 0 else len(s) + st
        if idx < 0 or idx >= len(s):
            return ""
        end = len(s) if ln is None else idx + max(ln, 0)
        return s[idx:end]

    return _string_map_fn(cc, a, sub)


@function("concat")
def _f_concat(cc, *args):
    """Dict-remap concat: works when at most ONE argument is a (dict) column
    and the rest are string literals — the common SQL pattern. Column-column
    concat would need a cross-product dictionary (planner-gated, later)."""
    col_args = [a for a in args if a.dict is not None]
    if len(col_args) > 1:
        raise NotImplementedError("concat of multiple string columns")
    for a in args:
        if a.dict is None and not isinstance(a.data, (str, int, float, bool)):
            raise NotImplementedError(
                "concat requires string literals / one string column "
                f"(got a {a.type} column)"
            )
    if not col_args:
        return EVal("".join(str(a.data) for a in args), None, T.VARCHAR)
    col = col_args[0]

    def f(s):
        return "".join(s if a is col else str(a.data) for a in args)

    return _string_map_fn(cc, col, f)


@function("length")
def _f_length(cc, a):
    assert a.dict is not None, "length() needs a string column"
    lens = np.fromiter((len(str(v)) for v in a.dict.values),
                       count=len(a.dict), dtype=np.int32)
    n = max(len(a.dict), 1)
    lut = jnp.asarray(lens) if len(a.dict) else jnp.zeros((1,), jnp.int32)
    return EVal(lut[jnp.clip(a.data, 0, n - 1)], a.valid, T.INT)


@function("trim")
def _f_trim(cc, a):
    return _string_map_fn(cc, a, str.strip)


@function("ltrim")
def _f_ltrim(cc, a):
    return _string_map_fn(cc, a, str.lstrip)


@function("rtrim")
def _f_rtrim(cc, a):
    return _string_map_fn(cc, a, str.rstrip)


@function("replace")
def _f_replace(cc, a, old, new):
    o, n = str(old.data), str(new.data)
    return _string_map_fn(cc, a, lambda s: s.replace(o, n))


@function("ends_with")
def _f_ends_with(cc, a, suf):
    p = str(suf.data)
    return _string_bool_fn(cc, a, lambda s: str(s).endswith(p))


@function("round")
def _f_round(cc, a, nd=None):
    digits = 0 if nd is None else int(nd.data)
    if a.type.is_decimal:
        s = a.type.scale
        if digits >= s:
            return a
        q = 10 ** (s - digits)
        d = jnp.asarray(a.data, jnp.int64)
        # round-half-away-from-zero on scaled ints
        r = jnp.where(d >= 0, (d + q // 2) // q, -((-d + q // 2) // q)) * q
        return EVal(r, a.valid, a.type)
    d = jnp.asarray(a.data, jnp.float64)
    f = 10.0 ** digits
    # SQL rounds half away from zero (jnp.round is banker's half-to-even)
    r = jnp.sign(d) * jnp.floor(jnp.abs(d) * f + 0.5) / f
    return EVal(r, a.valid, T.DOUBLE)


@function("floor")
def _f_floor(cc, a):
    d = _to_numeric(a, T.DOUBLE)
    return EVal(jnp.floor(d), a.valid, T.DOUBLE)


@function("ceil")
def _f_ceil(cc, a):
    d = _to_numeric(a, T.DOUBLE)
    return EVal(jnp.ceil(d), a.valid, T.DOUBLE)


@function("sqrt")
def _f_sqrt(cc, a):
    d = _to_numeric(a, T.DOUBLE)
    neg = d < 0
    out = jnp.sqrt(jnp.where(neg, 0.0, d))
    return EVal(out, _and_valid(a.valid, ~neg), T.DOUBLE)


@function("power")
def _f_power(cc, a, b):
    da = _to_numeric(a, T.DOUBLE)
    db = _to_numeric(b, T.DOUBLE)
    return EVal(jnp.power(da, db), _and_valid(a.valid, b.valid), T.DOUBLE)


@function("exp")
def _f_exp(cc, a):
    return EVal(jnp.exp(_to_numeric(a, T.DOUBLE)), a.valid, T.DOUBLE)


@function("ln")
def _f_ln(cc, a):
    d = _to_numeric(a, T.DOUBLE)
    bad = d <= 0
    return EVal(jnp.log(jnp.where(bad, 1.0, d)), _and_valid(a.valid, ~bad), T.DOUBLE)


@function("greatest")
def _f_greatest(cc, *args):
    ct = args[0].type
    for x in args[1:]:
        ct = T.common_numeric_type(ct, x.type)
    d = _to_numeric(args[0], ct)
    v = args[0].valid
    for x in args[1:]:
        d = jnp.maximum(d, _to_numeric(x, ct))
        v = _and_valid(v, x.valid)
    return EVal(d, v, ct)


@function("least")
def _f_least(cc, *args):
    ct = args[0].type
    for x in args[1:]:
        ct = T.common_numeric_type(ct, x.type)
    d = _to_numeric(args[0], ct)
    v = args[0].valid
    for x in args[1:]:
        d = jnp.minimum(d, _to_numeric(x, ct))
        v = _and_valid(v, x.valid)
    return EVal(d, v, ct)


@function("datediff")
def _f_datediff(cc, a, b):
    a = _lit_as_date_if_str(a)
    b = _lit_as_date_if_str(b)
    return EVal(
        jnp.asarray(_as_days(a), jnp.int32) - jnp.asarray(_as_days(b), jnp.int32),
        _and_valid(a.valid, b.valid), T.INT,
    )


@function("dayofweek", scope=DATE_PART)
def _f_dayofweek(cc, a):
    a = _lit_as_date_if_str(a)
    # 1970-01-01 was a Thursday; SQL convention: 1=Sunday .. 7=Saturday
    days = jnp.asarray(_as_days(a), jnp.int64)
    return EVal(((days + 4) % 7 + 1).astype(jnp.int32), a.valid, T.INT)


@function("quarter", scope=DATE_PART)
def _f_quarter(cc, a):
    a = _lit_as_date_if_str(a)
    y, m, d = _civil_from_days(_as_days(a))
    return EVal((m - 1) // 3 + 1, a.valid, T.INT)


def eval_expr(chunk: Chunk, e: Expr) -> EVal:
    return ExprCompiler(chunk).eval(e)


def eval_predicate(chunk: Chunk, e: Expr) -> jnp.ndarray:
    return ExprCompiler(chunk).eval_predicate(e)
