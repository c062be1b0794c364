"""Scalar function breadth wave 3: closing on the reference registry.

Families and naming follow gensrc/script/functions.py (993 builtins) with
per-family behavior from be/src/exprs/{math,string,time,encryption}_functions*
and be/src/exprs/function_helper.h, re-designed for the trace-time dict
string model (see functions_ext.py header for the lowering rules).

Notable lowering choices:
- now()/curdate() snapshot at TRACE time (classic statement-snapshot
  semantics); plans containing them re-trace per execution.
- date_format builds a whole-range LUT dictionary from catalog bounds (the
  bounded-domain trick: formatted strings for every date in [lo, hi] are a
  trace-time constant table) — unbounded date columns raise.
- rand() is a deterministic splitmix64 stream seeded by config rand_seed
  (reproducible traces; the reference's per-query seed behaves the same way
  within one query).
"""

from __future__ import annotations

import base64
import datetime
import hashlib
import math
import urllib.parse

import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..column.dict_encoding import StringDict
from .compile import (
    DATE_PART, EVal, _and_valid, _as_days, _civil_from_days, _common,
    _days_from_civil, _lit_as_date_if_str, _string_bool_fn, _string_map_fn,
    _to_numeric, function,
)
from .functions_ext import _lit_str, _string_int_fn, _unary_double


# --- math --------------------------------------------------------------------


def _register_math():
    for name, op in [
        ("asinh", jnp.arcsinh), ("acosh", jnp.arccosh), ("atanh", jnp.arctanh),
        ("sec", lambda x: 1.0 / jnp.cos(x)), ("csc", lambda x: 1.0 / jnp.sin(x)),
        ("dsqrt", jnp.sqrt), ("dexp", jnp.exp), ("dlog10", jnp.log10),
    ]:
        function(name)(_unary_double(op))


_register_math()


@function("pow")
def _f_pow(cc, a, b):
    return cc.call("power", a, b)


@function("dpow")
def _f_dpow(cc, a, b):
    return cc.call("power", a, b)


@function("fpow")
def _f_fpow(cc, a, b):
    return cc.call("power", a, b)


@function("fmod")
def _f_fmod(cc, a, b):
    return cc.call("mod", a, b)


@function("dround")
def _f_dround(cc, a, b=None):
    return cc.call("round", a, b) if b is not None else cc.call("round", a)


@function("dfloor")
def _f_dfloor(cc, a):
    return cc.call("floor", a)


@function("dceil")
def _f_dceil(cc, a):
    return cc.call("ceil", a)


@function("bit_count")
def _f_bit_count(cc, a):
    d = jnp.asarray(_to_numeric(a, T.BIGINT), jnp.uint64)
    # SWAR popcount (no scatter, fuses into the surrounding program)
    m1, m2, m4 = jnp.uint64(0x5555555555555555), jnp.uint64(
        0x3333333333333333), jnp.uint64(0x0F0F0F0F0F0F0F0F)
    d = d - ((d >> 1) & m1)
    d = (d & m2) + ((d >> 2) & m2)
    d = (d + (d >> 4)) & m4
    out = (d * jnp.uint64(0x0101010101010101)) >> 56
    return EVal(jnp.asarray(out, jnp.int64), a.valid, T.BIGINT)


_RAND_CALLS = [0]


def _rand_impl(cc):
    from ..runtime.config import config

    # distinct stream per rand() OCCURRENCE (two rand() in one SELECT must
    # not correlate); the counter is trace-time state, baked per program
    _RAND_CALLS[0] += 1
    seed = (int(config.get("rand_seed"))
            + _RAND_CALLS[0] * 0x9E3779B97F4A7C15) % (1 << 63)
    n = cc.chunk.capacity
    z = jnp.arange(n, dtype=jnp.uint64) + jnp.uint64(seed)
    z = (z ^ (z >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> 27)) * jnp.uint64(0x94D049BB133111EB)
    z = z ^ (z >> 31)
    return EVal(jnp.asarray(z >> jnp.uint64(11), jnp.float64)
                / float(1 << 53), None, T.DOUBLE)


@function("rand")
def _f_rand(cc):
    return _rand_impl(cc)


@function("random")
def _f_random(cc):
    return _rand_impl(cc)


# --- null handling / conditionals --------------------------------------------


@function("isnull")
def _f_isnull(cc, a):
    return cc.call("is_null", a)


@function("isnotnull")
def _f_isnotnull(cc, a):
    return cc.call("is_not_null", a)


@function("nvl2")
def _f_nvl2(cc, a, b, c):
    """nvl2(x, if_not_null, if_null)."""
    return cc.call("if", cc.call("is_not_null", a), b, c)


@function("zeroifnull")
def _f_zeroifnull(cc, a):
    from .ir import Lit as _L  # noqa: F401 (doc only)

    d = _to_numeric(a, a.type if a.type.is_numeric else T.BIGINT)
    if a.valid is None:
        return a
    return EVal(jnp.where(a.valid, d, jnp.zeros((), d.dtype)), None, a.type)


@function("nullifzero")
def _f_nullifzero(cc, a):
    d = _to_numeric(a, a.type if a.type.is_numeric else T.BIGINT)
    nz = d != 0
    valid = nz if a.valid is None else (a.valid & nz)
    return EVal(d, valid, a.type)


# --- date & time -------------------------------------------------------------


def _trace_now():
    return datetime.datetime.now()


def _const_date(cc, d: datetime.date):
    days = (d - datetime.date(1970, 1, 1)).days
    return EVal(jnp.asarray(days, jnp.int32), None, T.DATE)


def _const_datetime(cc, dt: datetime.datetime):
    # naive local time, matching str_to_date/DATETIME storage convention
    epoch = datetime.datetime(1970, 1, 1)
    us = int((dt - epoch).total_seconds() * 1_000_000)
    return EVal(jnp.asarray(us, jnp.int64), None, T.DATETIME)


@function("curdate")
def _f_curdate(cc):
    return _const_date(cc, _trace_now().date())


@function("current_date")
def _f_current_date(cc):
    return _const_date(cc, _trace_now().date())


@function("now")
def _f_now(cc):
    return _const_datetime(cc, _trace_now())


@function("current_timestamp")
def _f_current_timestamp(cc):
    return _const_datetime(cc, _trace_now())


@function("localtimestamp")
def _f_localtimestamp(cc):
    return _const_datetime(cc, _trace_now())


@function("utc_timestamp")
def _f_utc_timestamp(cc):
    return _const_datetime(cc, datetime.datetime.utcnow())


@function("weekday")
def _f_weekday(cc, a):
    """0 = Monday (MySQL WEEKDAY)."""
    a = _lit_as_date_if_str(a)
    days = _as_days(a)
    return EVal(jnp.asarray((days + 3) % 7, jnp.int32), a.valid, T.INT)


@function("day_of_week")
def _f_day_of_week(cc, a):
    return cc.call("dayofweek", a)


@function("dayofweek_iso")
def _f_dayofweek_iso(cc, a):
    """1 = Monday .. 7 = Sunday (ISO-8601)."""
    a = _lit_as_date_if_str(a)
    days = _as_days(a)
    return EVal(jnp.asarray((days + 3) % 7 + 1, jnp.int32), a.valid, T.INT)


@function("day_of_month")
def _f_day_of_month(cc, a):
    return cc.call("dayofmonth", a)


@function("day_of_year")
def _f_day_of_year(cc, a):
    return cc.call("dayofyear", a)


@function("week_of_year")
def _f_week_of_year(cc, a):
    return cc.call("weekofyear", a)


@function("yearweek", scope=DATE_PART)
def _f_yearweek(cc, a):
    """ISO pair: the year of the week's Thursday x 100 + ISO week (keeps
    year boundaries consistent with weekofyear — late-December dates in ISO
    week 1 report the NEXT year, 202153-style nonexistent weeks can't
    occur)."""
    a = _lit_as_date_if_str(a)
    days = _as_days(a)
    thu = days - (days + 3) % 7 + 3
    y, _m, _d = _civil_from_days(thu)
    wk = cc.call("weekofyear", a)
    return EVal(y * 100 + wk.data, _and_valid(a.valid, wk.valid), T.INT)


@function("microsecond")
def _f_microsecond(cc, a):
    if a.type.kind is not T.TypeKind.DATETIME:
        raise TypeError("microsecond() expects DATETIME")
    return EVal(jnp.asarray(a.data % 1_000_000, jnp.int32), a.valid, T.INT)


@function("time_to_sec")
def _f_time_to_sec(cc, a):
    """Seconds since midnight of a DATETIME."""
    if a.type.kind is not T.TypeKind.DATETIME:
        raise TypeError("time_to_sec() expects DATETIME")
    us_per_day = 86_400_000_000
    return EVal(
        jnp.asarray((a.data % us_per_day) // 1_000_000, jnp.int64),
        a.valid, T.BIGINT)


def _register_quarter_ms_us():
    from .compile import _FUNCTIONS

    def quarters_add(cc, a, n):
        return cc.call("months_add", a, EVal(
            jnp.asarray(n.data) * 3, n.valid, T.INT))

    def quarters_sub(cc, a, n):
        return cc.call("months_sub", a, EVal(
            jnp.asarray(n.data) * 3, n.valid, T.INT))

    function("quarters_add")(quarters_add)
    function("quarters_sub")(quarters_sub)

    def us_shift(scale):
        def f(cc, a, n):
            if a.type.kind is not T.TypeKind.DATETIME:
                raise TypeError("expects DATETIME")
            nd = jnp.asarray(_to_numeric(n, T.BIGINT), jnp.int64)
            return EVal(a.data + nd * scale, _and_valid(a.valid, n.valid),
                        T.DATETIME)
        return f

    for name, scale in [("milliseconds_add", 1000),
                        ("microseconds_add", 1),
                        ("milliseconds_sub", -1000),
                        ("microseconds_sub", -1)]:
        function(name)(us_shift(scale))


_register_quarter_ms_us()


def _dt_to_us(v: EVal):
    """DATE/DATETIME -> microseconds since epoch."""
    if v.type.kind is T.TypeKind.DATETIME:
        return jnp.asarray(v.data, jnp.int64)
    if v.type.kind is T.TypeKind.DATE:
        return jnp.asarray(v.data, jnp.int64) * 86_400_000_000
    raise TypeError(f"expected date/datetime, got {v.type}")


def _register_diffs():
    """<unit>s_diff(a, b) = count of whole units in a - b (reference:
    be/src/exprs/time_functions.cpp *_diff family)."""
    us = {"seconds": 1_000_000, "minutes": 60_000_000,
          "hours": 3_600_000_000, "days": 86_400_000_000,
          "milliseconds": 1_000, "weeks": 7 * 86_400_000_000}

    def make(scale):
        def f(cc, a, b):
            a = _lit_as_date_if_str(a)
            b = _lit_as_date_if_str(b)
            d = _dt_to_us(a) - _dt_to_us(b)
            # truncate toward zero (MySQL semantics)
            q = jnp.where(d >= 0, d // scale, -((-d) // scale))
            return EVal(q, _and_valid(a.valid, b.valid), T.BIGINT)
        return f

    for unit, scale in us.items():
        function(f"{unit}_diff")(make(scale))

    def months_between(cc, a, b, whole_only=True):
        a = _lit_as_date_if_str(a)
        b = _lit_as_date_if_str(b)
        ya, ma, da = _civil_from_days(_as_days(a))
        yb, mb, db = _civil_from_days(_as_days(b))
        months = (ya - yb) * 12 + (ma - mb)
        # subtract one when the day-of-month hasn't been reached
        adj = jnp.where((months > 0) & (da < db), 1, 0)
        adj = adj + jnp.where((months < 0) & (da > db), -1, 0)
        return EVal(jnp.asarray(months - adj, jnp.int64),
                    _and_valid(a.valid, b.valid), T.BIGINT)

    function("months_diff")(months_between)

    def years_diff(cc, a, b):
        m = months_between(cc, a, b)
        q = jnp.where(m.data >= 0, m.data // 12, -((-m.data) // 12))
        return EVal(q, m.valid, T.BIGINT)

    function("years_diff")(years_diff)

    def quarters_diff(cc, a, b):
        m = months_between(cc, a, b)
        q = jnp.where(m.data >= 0, m.data // 3, -((-m.data) // 3))
        return EVal(q, m.valid, T.BIGINT)

    function("quarters_diff")(quarters_diff)


_register_diffs()


@function("date_diff")
def _f_date_diff(cc, unit, a, b):
    u = _lit_str(unit, "date_diff").lower().rstrip("s")
    table = {"second": "seconds_diff", "minute": "minutes_diff",
             "hour": "hours_diff", "day": "days_diff", "week": "weeks_diff",
             "month": "months_diff", "year": "years_diff",
             "quarter": "quarters_diff", "millisecond": "milliseconds_diff"}
    if u not in table:
        raise NotImplementedError(f"date_diff unit {u!r}")
    return cc.call(table[u], a, b)


@function("next_day")
def _f_next_day(cc, a, dow):
    """Smallest date > a falling on weekday `dow` ('Monday'/'Mon'/'Mo')."""
    a = _lit_as_date_if_str(a)
    names = ["monday", "tuesday", "wednesday", "thursday", "friday",
             "saturday", "sunday"]
    w = _lit_str(dow, "next_day").lower()
    target = next((i for i, n in enumerate(names)
                   if n.startswith(w) and len(w) >= 2), None)
    if target is None:
        raise ValueError(f"next_day: bad weekday {w!r}")
    days = _as_days(a)
    cur = (days + 3) % 7  # 0=Monday
    delta = (target - cur - 1) % 7 + 1
    return EVal(jnp.asarray(days + delta, jnp.int32), a.valid, T.DATE)


@function("previous_day")
def _f_previous_day(cc, a, dow):
    a = _lit_as_date_if_str(a)
    names = ["monday", "tuesday", "wednesday", "thursday", "friday",
             "saturday", "sunday"]
    w = _lit_str(dow, "previous_day").lower()
    target = next((i for i, n in enumerate(names)
                   if n.startswith(w) and len(w) >= 2), None)
    if target is None:
        raise ValueError(f"previous_day: bad weekday {w!r}")
    days = _as_days(a)
    cur = (days + 3) % 7
    delta = (cur - target - 1) % 7 + 1
    return EVal(jnp.asarray(days - delta, jnp.int32), a.valid, T.DATE)


@function("date_floor")
def _f_date_floor(cc, unit, a):
    return cc.call("date_trunc", unit, a)


@function("date_slice")
def _f_date_slice(cc, unit, a):
    return cc.call("date_trunc", unit, a)


@function("time_slice")
def _f_time_slice(cc, unit, a):
    return cc.call("date_trunc", unit, a)


@function("add_months")
def _f_add_months(cc, a, n):
    return cc.call("months_add", a, n)


@function("date_format")
def _f_date_format(cc, a, fmt):
    """MySQL %-format over a STATS-BOUNDED date/datetime column: format every
    value in [lo, hi] days at trace time into a LUT dictionary (the bounded
    -domain trick; unbounded columns raise — run ANALYZE/ingest stats)."""
    a0 = a
    a = _lit_as_date_if_str(a)
    f = _lit_str(fmt, "date_format")
    trans = {"%Y": "%Y", "%y": "%y", "%m": "%m", "%c": "%-m", "%d": "%d",
             "%e": "%-d", "%H": "%H", "%i": "%M", "%s": "%S", "%S": "%S",
             "%T": "%H:%M:%S", "%f": "%f", "%j": "%j", "%W": "%A",
             "%a": "%a", "%b": "%b", "%M": "%B", "%%": "%%"}
    py = ""
    i = 0
    while i < len(f):
        if f[i] == "%" and i + 1 < len(f):
            tok = f[i:i + 2]
            py += trans.get(tok, tok)
            i += 2
        else:
            py += f[i]
            i += 1
    if (a0.type.kind is T.TypeKind.DATETIME
            and any(t in f for t in ("%H", "%i", "%s", "%S", "%T", "%f"))):
        # the per-DAY LUT cannot carry time-of-day; rendering 00:00:00
        # silently would be a wrong answer
        raise NotImplementedError(
            "date_format time tokens on DATETIME are not supported "
            "(day-granularity tokens only)")
    db = None
    if a.bounds is not None:
        lo, hi = int(a.bounds[0]), int(a.bounds[1])
        if a0.type.kind is T.TypeKind.DATETIME:
            lo, hi = lo // 86_400_000_000, hi // 86_400_000_000
        if hi - lo <= 200_000:
            db = (lo, hi)
    if db is None:
        raise NotImplementedError(
            "date_format needs bounded date stats (scan a stored table)")
    lo, hi = db
    epoch = datetime.date(1970, 1, 1)
    vals = []
    for d in range(lo, hi + 1):
        dt = epoch + datetime.timedelta(days=int(d))
        vals.append(datetime.datetime(dt.year, dt.month, dt.day).strftime(py))
    dct, codes = StringDict.from_strings(vals)
    lut = jnp.asarray(codes)
    days = jnp.clip(_as_days(a) - lo, 0, hi - lo)
    return EVal(lut[days], a.valid, T.VARCHAR, dct)


# --- strings -----------------------------------------------------------------


@function("mid")
def _f_mid(cc, a, start, length=None):
    return (cc.call("substr", a, start, length) if length is not None
            else cc.call("substr", a, start))


@function("position")
def _f_position(cc, a, b):
    return cc.call("locate", a, b)


@function("bit_length")
def _f_bit_length(cc, a):
    return _string_int_fn(cc, a, lambda s: 8 * len(s.encode()))


@function("octet_length")
def _f_octet_length(cc, a):
    return _string_int_fn(cc, a, lambda s: len(s.encode()))


@function("to_base64")
def _f_to_base64(cc, a):
    return _string_map_fn(
        cc, a, lambda s: base64.b64encode(s.encode()).decode())


@function("base64_encode")
def _f_base64_encode(cc, a):
    return cc.call("to_base64", a)


def _b64dec(s: str) -> str:
    try:
        return base64.b64decode(s, validate=False).decode("utf-8", "replace")
    except Exception:  # noqa: BLE001 — bad input -> empty (reference: NULL)
        return ""


@function("from_base64")
def _f_from_base64(cc, a):
    return _string_map_fn(cc, a, _b64dec)


@function("base64_decode_string")
def _f_base64_decode_string(cc, a):
    return cc.call("from_base64", a)


@function("unhex")
def _f_unhex(cc, a):
    def f(s):
        try:
            return bytes.fromhex(s).decode("utf-8", "replace")
        except ValueError:
            return ""
    return _string_map_fn(cc, a, f)


@function("hex_decode_string")
def _f_hex_decode_string(cc, a):
    return cc.call("unhex", a)


@function("sha1")
def _f_sha1(cc, a):
    return _string_map_fn(
        cc, a, lambda s: hashlib.sha1(s.encode()).hexdigest())


@function("sm3")
def _f_sm3(cc, a):
    # no SM3 in hashlib guarantees; expose via supported digest when present
    if "sm3" not in hashlib.algorithms_available:
        raise NotImplementedError("sm3 digest unavailable in this build")
    return _string_map_fn(
        cc, a, lambda s: hashlib.new("sm3", s.encode()).hexdigest())


def _murmur3_32(data: bytes, seed: int = 0) -> int:
    """Faithful MurmurHash3 x86_32 (reference: be/src/util/hash_util.hpp)."""
    c1, c2 = 0xCC9E2D51, 0x1B873593
    h = seed & 0xFFFFFFFF
    n = len(data)
    rounded = n - n % 4
    for i in range(0, rounded, 4):
        k = int.from_bytes(data[i:i + 4], "little")
        k = (k * c1) & 0xFFFFFFFF
        k = ((k << 15) | (k >> 17)) & 0xFFFFFFFF
        k = (k * c2) & 0xFFFFFFFF
        h ^= k
        h = ((h << 13) | (h >> 19)) & 0xFFFFFFFF
        h = (h * 5 + 0xE6546B64) & 0xFFFFFFFF
    k = 0
    tail = data[rounded:]
    if len(tail) >= 3:
        k ^= tail[2] << 16
    if len(tail) >= 2:
        k ^= tail[1] << 8
    if len(tail) >= 1:
        k ^= tail[0]
        k = (k * c1) & 0xFFFFFFFF
        k = ((k << 15) | (k >> 17)) & 0xFFFFFFFF
        k = (k * c2) & 0xFFFFFFFF
        h ^= k
    h ^= n
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & 0xFFFFFFFF
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & 0xFFFFFFFF
    h ^= h >> 16
    return h


@function("murmur_hash3_32")
def _f_murmur_hash3_32(cc, a):
    def signed(s):
        h = _murmur3_32(s.encode())
        return h - (1 << 32) if h >= (1 << 31) else h

    return _string_int_fn(cc, a, signed)


def _fnv1a64(data: bytes) -> int:
    h = 0xCBF29CE484222325
    for b in data:
        h ^= b
        h = (h * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
    return h - (1 << 64) if h >= (1 << 63) else h


@function("fnv_hash")
def _f_fnv_hash(cc, a):
    return _string_int_fn(cc, a, lambda s: _fnv1a64(s.encode()), T.BIGINT)


@function("translate")
def _f_translate(cc, a, from_s, to_s):
    fs = _lit_str(from_s, "translate")
    ts = _lit_str(to_s, "translate")
    table = str.maketrans(fs[:len(ts)], ts[:len(fs)], fs[len(ts):])
    return _string_map_fn(cc, a, lambda s: s.translate(table))


@function("url_encode")
def _f_url_encode(cc, a):
    return _string_map_fn(cc, a, lambda s: urllib.parse.quote(s, safe=""))


@function("url_decode")
def _f_url_decode(cc, a):
    return _string_map_fn(cc, a, urllib.parse.unquote)


@function("parse_url")
def _f_parse_url(cc, a, part):
    p = _lit_str(part, "parse_url").upper()

    def f(s):
        u = urllib.parse.urlparse(s)
        return {
            "PROTOCOL": u.scheme, "HOST": u.hostname or "",
            "PATH": u.path, "QUERY": u.query, "REF": u.fragment,
            "AUTHORITY": u.netloc,
            "PORT": str(u.port) if u.port else "",
            "USERINFO": (u.username or "") if u.username else "",
            "FILE": u.path + (("?" + u.query) if u.query else ""),
        }.get(p, "")

    return _string_map_fn(cc, a, f)


@function("substring_index")
def _f_substring_index(cc, a, delim, count):
    d = _lit_str(delim, "substring_index")
    k = int(count.data)

    def f(s):
        if not d or k == 0:
            return ""
        parts = s.split(d)
        if k > 0:
            return d.join(parts[:k])
        return d.join(parts[k:])

    return _string_map_fn(cc, a, f)


@function("field")
def _f_field(cc, a, *options):
    opts = [_lit_str(o, "field") for o in options]

    def f(s):
        try:
            return opts.index(s) + 1
        except ValueError:
            return 0

    return _string_int_fn(cc, a, f)


@function("elt")
def _f_elt(cc, n, *options):
    """elt(index, s1, s2, ...) — index column selects among literals."""
    opts = [_lit_str(o, "elt") for o in options]
    dct, codes = StringDict.from_strings(opts + [""])
    lut = jnp.asarray(codes)
    idx = jnp.asarray(_to_numeric(n, T.BIGINT), jnp.int64)
    in_range = (idx >= 1) & (idx <= len(opts))
    code = lut[jnp.clip(jnp.where(in_range, idx - 1, len(opts)),
                        0, len(opts))]
    valid = _and_valid(n.valid, in_range) if n.valid is not None else in_range
    return EVal(code, valid, T.VARCHAR, dct)


@function("find_in_set")
def _f_find_in_set(cc, a, set_lit):
    items = _lit_str(set_lit, "find_in_set").split(",")

    def f(s):
        try:
            return items.index(s) + 1
        except ValueError:
            return 0

    return _string_int_fn(cc, a, f)


@function("soundex")
def _f_soundex(cc, a):
    codes = {**dict.fromkeys("BFPV", "1"), **dict.fromkeys("CGJKQSXZ", "2"),
             **dict.fromkeys("DT", "3"), "L": "4",
             **dict.fromkeys("MN", "5"), "R": "6"}

    def f(s):
        s = "".join(ch for ch in s.upper() if ch.isalpha())
        if not s:
            return ""
        out = s[0]
        prev = codes.get(s[0], "")
        for ch in s[1:]:
            c = codes.get(ch, "")
            if c and c != prev:
                out += c
            if ch not in "HW":
                prev = c
        return (out + "000")[:4]

    return _string_map_fn(cc, a, f)


@function("append_trailing_char_if_absent")
def _f_append_trailing(cc, a, ch):
    c = _lit_str(ch, "append_trailing_char_if_absent")

    def f(s):
        return s if s.endswith(c) else s + c

    return _string_map_fn(cc, a, f)


@function("quote")
def _f_quote(cc, a):
    def f(s):
        return "'" + s.replace("\\", "\\\\").replace("'", "\\'") + "'"
    return _string_map_fn(cc, a, f)


@function("strcmp")
def _f_strcmp(cc, a, b):
    """-1/0/1 comparison of two string columns (merged-dict rank compare)."""
    lt = cc.call("lt", a, b)
    gt = cc.call("gt", a, b)
    out = jnp.where(jnp.asarray(gt.data, jnp.bool_), 1,
                    jnp.where(jnp.asarray(lt.data, jnp.bool_), -1, 0))
    return EVal(jnp.asarray(out, jnp.int32), _and_valid(a.valid, b.valid),
                T.INT)


@function("ngram_search")
def _f_ngram_search(cc, a, pat, n):
    """4-gram similarity in [0,1] against a literal (reference:
    be/src/exprs/string_functions.cpp ngram_search)."""
    p = _lit_str(pat, "ngram_search")
    gram = int(n.data)

    def grams(s):
        return {s[i:i + gram] for i in range(max(len(s) - gram + 1, 0))}

    pg = grams(p)

    def f(s):
        sg = grams(s)
        if not sg or not pg:
            return 0.0
        return len(sg & pg) / max(len(pg), 1)

    assert a.dict is not None, "ngram_search needs a string column"
    vals = [f(str(s)) for s in a.dict.values]
    lut = jnp.asarray(np.asarray(vals, np.float64)) if vals else jnp.zeros(
        (1,), jnp.float64)
    nmax = max(len(a.dict), 1)
    out = lut[jnp.clip(a.data, 0, nmax - 1)]
    return EVal(out, a.valid, T.DOUBLE)


@function("levenshtein")
def _f_levenshtein(cc, a, b):
    """Edit distance against a literal second argument."""
    t = _lit_str(b, "levenshtein")

    def dist(s):
        if len(s) < len(t):
            return dist_rec(t, s)
        return dist_rec(s, t)

    def dist_rec(s, u):
        prev = list(range(len(u) + 1))
        for i, cs in enumerate(s):
            cur = [i + 1]
            for j, cu in enumerate(u):
                cur.append(min(prev[j + 1] + 1, cur[j] + 1,
                               prev[j] + (cs != cu)))
            prev = cur
        return prev[-1]

    return _string_int_fn(cc, a, dist, T.BIGINT)


# --- JSON-on-VARCHAR ---------------------------------------------------------


def _json_get(s: str, path: str):
    """Tiny $.a.b[0] JSON-path evaluator (reference get_json_* semantics:
    be/src/exprs/json_functions.cpp)."""
    import json as _json

    try:
        v = _json.loads(s)
    except Exception:  # noqa: BLE001
        return None
    if not path.startswith("$"):
        path = "$." + path
    i = 1
    while i < len(path) and v is not None:
        if path[i] == ".":
            j = i + 1
            while j < len(path) and path[j] not in ".[":
                j += 1
            key = path[i + 1:j]
            v = v.get(key) if isinstance(v, dict) else None
            i = j
        elif path[i] == "[":
            j = path.index("]", i)
            try:
                idx = int(path[i + 1:j])
            except ValueError:
                return None
            v = v[idx] if isinstance(v, list) and -len(v) <= idx < len(v) \
                else None
            i = j + 1
        else:
            return None
    return v


@function("get_json_string")
def _f_get_json_string(cc, a, path):
    p = _lit_str(path, "get_json_string")

    def f(s):
        v = _json_get(s, p)
        if v is None:
            return ""
        if isinstance(v, (dict, list)):
            import json as _json

            return _json.dumps(v, separators=(",", ":"))
        if isinstance(v, bool):
            return "true" if v else "false"
        return str(v)

    return _string_map_fn(cc, a, f)


@function("get_json_int")
def _f_get_json_int(cc, a, path):
    p = _lit_str(path, "get_json_int")

    def f(s):
        v = _json_get(s, p)
        try:
            return int(v)
        except (TypeError, ValueError):
            return 0

    return _string_int_fn(cc, a, f, T.BIGINT)


@function("get_json_double")
def _f_get_json_double(cc, a, path):
    p = _lit_str(path, "get_json_double")
    assert a.dict is not None, "get_json_double needs a string column"

    def f(s):
        v = _json_get(s, p)
        try:
            return float(v)
        except (TypeError, ValueError):
            return 0.0

    vals = [f(str(s)) for s in a.dict.values]
    lut = jnp.asarray(np.asarray(vals, np.float64)) if vals else jnp.zeros(
        (1,), jnp.float64)
    n = max(len(a.dict), 1)
    return EVal(lut[jnp.clip(a.data, 0, n - 1)], a.valid, T.DOUBLE)


@function("json_valid")
def _f_json_valid(cc, a):
    import json as _json

    def f(s):
        try:
            _json.loads(s)
            return True
        except Exception:  # noqa: BLE001
            return False

    return _string_bool_fn(cc, a, f)


# --- session / utility -------------------------------------------------------


def _const_str(cc, s: str):
    dct, codes = StringDict.from_strings([s])
    return EVal(jnp.asarray(codes[0]), None, T.VARCHAR, dct)


@function("version")
def _f_version(cc):
    return _const_str(cc, "8.0.33-starrocks-tpu")


@function("current_version")
def _f_current_version(cc):
    return _const_str(cc, "starrocks-tpu-0.3")


@function("connection_id")
def _f_connection_id(cc):
    return EVal(jnp.asarray(1, jnp.int64), None, T.BIGINT)


@function("database")
def _f_database(cc):
    return _const_str(cc, "default")


@function("schema")
def _f_schema(cc):
    return _const_str(cc, "default")


@function("user")
def _f_user(cc):
    return _const_str(cc, "root")


@function("current_user")
def _f_current_user(cc):
    return _const_str(cc, "root")


@function("session_user")
def _f_session_user(cc):
    return _const_str(cc, "root")


@function("typeof")
def _f_typeof(cc, a):
    return _const_str(cc, str(a.type).lower())


@function("ngram_search_case_insensitive")
def _f_ngram_search_ci(cc, a, b, *rest):
    return cc.call("ngram_search", cc.call("lower", a),
                   cc.call("lower", b), *rest)


@function("json_value")
def _f_json_value(cc, j, path):
    # the scalar-extraction form of the JSON-path family
    return cc.call("get_json_string", j, path)


@function("grouping")
def _f_grouping(cc, *args):
    # the analyzer lowers grouping()/grouping_id() over ROLLUP/CUBE/SETS
    # keys into __grouping_i marker columns; reaching the registry means
    # the call sat outside a grouping-sets aggregate
    raise ValueError(
        "grouping() is only valid over GROUP BY ROLLUP/CUBE/GROUPING SETS "
        "keys")


@function("grouping_id")
def _f_grouping_id(cc, *args):
    raise ValueError(
        "grouping_id() is only valid over GROUP BY ROLLUP/CUBE/GROUPING "
        "SETS keys")
