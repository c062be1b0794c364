"""Builtin wave 4: reference-name coverage for strings, hashes, datetime,
vector distances, arrays, JSON, and bitmap manipulation.

Reference behavior: the generated function table
(gensrc/script/functions.py) — names and semantics follow it; kernels are
re-designed for the trace-time dict/limb/plane layouts (string transforms
are constant LUT remaps, bitmap ops are dense-plane arithmetic)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..column.dict_encoding import StringDict
from .compile import (
    DATE_PART, EVal, _and_valid, _as_days, _days_from_civil,
    _string_bool_fn, _string_map_fn, function,
)
from .functions_ext import _lit_str, _string_int_fn
from .functions_wave3 import _const_str, _json_get, _rand_impl


def _bounded_value_strings(cc, a: EVal, render, fn_name: str,
                           max_domain: int = 1 << 18) -> EVal:
    """Numeric -> string via a STATS-BOUNDED LUT dictionary (the same
    bounded-domain contract as date_format: unbounded columns raise)."""
    if np.ndim(a.data) == 0 and not hasattr(a.data, "aval"):
        scale = 10 ** a.type.scale if a.type.is_decimal else 1
        return _const_str(cc, render(
            int(a.data) / scale if scale > 1 else a.data))
    if a.bounds is None:
        raise NotImplementedError(
            f"{fn_name} over unbounded columns — ingest stats/ANALYZE "
            "(the bounded-domain string contract)")
    lo, hi = int(a.bounds[0]), int(a.bounds[1])
    if hi - lo + 1 > max_domain:
        raise NotImplementedError(
            f"{fn_name}: value domain {hi - lo + 1} exceeds {max_domain}")
    scale = 10 ** a.type.scale if a.type.is_decimal else 1
    vals = [render((lo + i) / scale if scale > 1 else lo + i)
            for i in range(hi - lo + 1)]
    d, codes = StringDict.from_strings(vals)
    remap = jnp.asarray(codes)
    idx = jnp.clip(jnp.asarray(a.data, jnp.int64) - lo, 0, hi - lo)
    return EVal(remap[idx], a.valid, T.VARCHAR, d)


def _string_to_array_fn(cc, s: EVal, parts_fn) -> EVal:
    """str -> ARRAY<VARCHAR> via a per-dictionary-value parts LUT (the
    split() idiom generalized to any tokenizer)."""
    if s.dict is None and isinstance(s.data, str):
        parts = parts_fn(s.data)
        d, codes = StringDict.from_strings(parts)
        row = jnp.concatenate([
            jnp.asarray([len(parts)], jnp.int32),
            jnp.asarray(codes, jnp.int32)])
        data = jnp.broadcast_to(row[None, :],
                                (cc.chunk.capacity, row.shape[0]))
        return EVal(data, s.valid, T.ARRAY(T.VARCHAR), d)
    assert s.dict is not None, "string column required"
    all_parts = [list(parts_fn(str(v))) for v in s.dict.values]
    flat = [p for ps in all_parts for p in ps]
    d, codes = StringDict.from_strings(flat) if flat else (
        StringDict.from_values([]), np.zeros(0, np.int32))
    k = max((len(ps) for ps in all_parts), default=1) or 1
    lut = np.zeros((max(len(s.dict), 1), k + 1), np.int32)
    it = iter(np.asarray(codes).tolist())
    for i, ps in enumerate(all_parts):
        lut[i, 0] = len(ps)
        for j in range(len(ps)):
            lut[i, 1 + j] = next(it)
    idx = jnp.clip(jnp.asarray(s.data), 0, lut.shape[0] - 1)
    return EVal(jnp.asarray(lut)[idx], s.valid, T.ARRAY(T.VARCHAR), d)


def _alias(new: str, old: str):
    from .compile import _FUNCTIONS

    impl = _FUNCTIONS[old]
    _FUNCTIONS.setdefault(new, impl)


# --- string aliases / simple transforms --------------------------------------

_alias("substring", "substr")
_alias("trim_string", "trim")
_alias("ltrim_string", "ltrim")
_alias("rtrim_string", "rtrim")
_alias("replace_old", "replace")
_alias("ceiling", "ceil")
_alias("dlog1", "ln")
_alias("crc32_hash", "crc32")
_alias("md5sum", "md5")
_alias("date_add", "adddate")
_alias("str2date", "str_to_date")
_alias("localtime", "now")
_alias("to_datetime", "from_unixtime")


@function("char")
def _f_char(cc, *args):
    """CHAR(n, ...): code points -> string (literal args)."""
    chars = []
    for a in args:
        chars.append(chr(int(a.data) & 0x10FFFF))
    return _const_str(cc, "".join(chars))


@function("bin")
def _f_bin(cc, a):
    if not a.type.is_integer:
        raise TypeError("bin expects an integer")
    # bounded-width binary render via per-bit string assembly would need a
    # data-dependent dict; serve the common literal/lowcard case via stats
    if np.ndim(a.data) == 0 and not hasattr(a.data, "aval"):
        return _const_str(cc, bin(int(a.data))[2:])
    raise NotImplementedError("bin over columns: cast via conv() patterns")


@function("conv")
def _f_conv(cc, a, fb, tb):
    f_base, t_base = int(fb.data), int(tb.data)

    def f(s):
        try:
            v = int(str(s), f_base)
        except ValueError:
            return "0"
        if t_base == 10:
            return str(v)
        digits = "0123456789ABCDEFGHIJKLMNOPQRSTUVWXYZ"
        neg, v = v < 0, abs(v)
        out = ""
        while True:
            out = digits[v % t_base] + out
            v //= t_base
            if v == 0:
                break
        return ("-" if neg else "") + out

    return _string_map_fn(cc, a, f)


@function("money_format")
def _f_money_format(cc, a):
    # numeric -> '1,234.56': data-dependent strings, bounded domains only
    # (same contract as date_format)
    return _bounded_value_strings(cc, a, lambda v: f"{float(v):,.2f}",
                                  "money_format")


@function("format_bytes")
def _f_format_bytes(cc, a):
    def f(v):
        x = float(v)
        for unit in ("B", "KB", "MB", "GB", "TB", "PB"):
            if abs(x) < 1024 or unit == "PB":
                return f"{x:.2f} {unit}" if unit != "B" else f"{int(x)} B"
            x /= 1024
        return f"{x:.2f} PB"

    return _bounded_value_strings(cc, a, f, "format_bytes")


@function("url_extract_host")
def _f_url_extract_host(cc, a):
    from urllib.parse import urlparse

    return _string_map_fn(cc, a, lambda s: urlparse(s).hostname or "")


@function("url_extract_parameter")
def _f_url_extract_parameter(cc, a, name):
    from urllib.parse import parse_qs, urlparse

    key = _lit_str(name, "url_extract_parameter")

    def f(s):
        vals = parse_qs(urlparse(s).query).get(key)
        return vals[0] if vals else ""

    return _string_map_fn(cc, a, f)


@function("tokenize")
def _f_tokenize(cc, mode, a=None):
    """tokenize('standard', s): lowercased word split as ARRAY<VARCHAR>
    (reference: the inverted-index analyzer surface)."""
    import re as _re

    if a is None:
        mode, a = None, mode
    return _string_to_array_fn(
        cc, a, lambda s: _re.findall(r"[a-z0-9]+", str(s).lower()))


# --- hashes / ids -------------------------------------------------------------


def _xxh64_py(data: bytes, seed: int = 0) -> int:
    """xxHash64 (public spec; round/merge constants per the algorithm)."""
    P1, P2, P3, P4, P5 = (
        0x9E3779B185EBCA87, 0xC2B2AE3D27D4EB4F, 0x165667B19E3779F9,
        0x85EBCA77C2B2AE63, 0x27D4EB2F165667C5)
    M = (1 << 64) - 1

    def rotl(x, r):
        return ((x << r) | (x >> (64 - r))) & M

    n = len(data)
    if n >= 32:
        v1 = (seed + P1 + P2) & M
        v2 = (seed + P2) & M
        v3 = seed & M
        v4 = (seed - P1) & M
        i = 0
        while i <= n - 32:
            for j, v in enumerate((v1, v2, v3, v4)):
                lane = int.from_bytes(data[i + 8 * j:i + 8 * j + 8],
                                      "little")
                v = (v + lane * P2) & M
                v = (rotl(v, 31) * P1) & M
                if j == 0:
                    v1 = v
                elif j == 1:
                    v2 = v
                elif j == 2:
                    v3 = v
                else:
                    v4 = v
            i += 32
        h = (rotl(v1, 1) + rotl(v2, 7) + rotl(v3, 12) + rotl(v4, 18)) & M
        for v in (v1, v2, v3, v4):
            v = (rotl((v * P2) & M, 31) * P1) & M  # mergeRound
            h = ((h ^ v) * P1 + P4) & M
    else:
        h = (seed + P5) & M
        i = 0
    h = (h + n) & M
    while i <= n - 8:
        lane = int.from_bytes(data[i:i + 8], "little")
        h ^= (rotl((lane * P2) & M, 31) * P1) & M
        h = (rotl(h, 27) * P1 + P4) & M
        i += 8
    if i <= n - 4:
        h ^= (int.from_bytes(data[i:i + 4], "little") * P1) & M
        h = (rotl(h, 23) * P2 + P3) & M
        i += 4
    while i < n:
        h ^= (data[i] * P5) & M
        h = (rotl(h, 11) * P1) & M
        i += 1
    h ^= h >> 33
    h = (h * P2) & M
    h ^= h >> 29
    h = (h * P3) & M
    h ^= h >> 32
    return h


def _as_hash_bytes(s):
    return str(s).encode()


@function("xx_hash64")
def _f_xx_hash64(cc, a):
    def f(s):
        v = _xxh64_py(_as_hash_bytes(s))
        return v - (1 << 64) if v >= (1 << 63) else v

    return _string_int_fn(cc, a, f, T.BIGINT)


_alias("xx_hash3_64", "xx_hash64")  # reference alias surface


@function("xx_hash32")
def _f_xx_hash32(cc, a):
    return _string_int_fn(
        cc, a, lambda s: _xxh64_py(_as_hash_bytes(s)) & 0xFFFFFFFF, T.BIGINT)


@function("md5sum_numeric")
def _f_md5sum_numeric(cc, a):
    import hashlib

    def f(s):
        d = hashlib.md5(str(s).encode()).digest()
        v = int.from_bytes(d[:8], "big")
        return v - (1 << 64) if v >= (1 << 63) else v

    return _string_int_fn(cc, a, f, T.BIGINT)


@function("inet_aton")
def _f_inet_aton(cc, a):
    def f(s):
        try:
            parts = [int(p) for p in str(s).split(".")]
            if len(parts) != 4 or any(not 0 <= p <= 255 for p in parts):
                return 0
            return (parts[0] << 24) | (parts[1] << 16) | (parts[2] << 8) \
                | parts[3]
        except ValueError:
            return 0

    return _string_int_fn(cc, a, f, T.BIGINT)


@function("uuid_numeric")
def _f_uuid_numeric(cc):
    r = _rand_impl(cc)  # seeded splitmix stream
    return EVal(jnp.asarray(
        jnp.asarray(r.data * (1 << 62), jnp.int64)), None, T.BIGINT)


_alias("uuid_v7_numeric", "uuid_numeric")


@function("dict_encode")
def _f_dict_encode(cc, a):
    """Expose the dictionary code of a string value (low-cardinality
    acceleration surface; reference: global-dict rewrite)."""
    if a.dict is None:
        raise TypeError("dict_encode expects a dict-encoded string column")
    return EVal(jnp.asarray(a.data, jnp.int64), a.valid, T.BIGINT)


@function("materialize")
def _f_materialize(cc, a):
    return a


@function("host_name")
def _f_host_name(cc):
    # platform.node() == uname nodename: same value as gethostname()
    # without pulling socket into the expression layer (the boundary
    # manifest reserves sockets for the runtime service modules)
    import platform

    return _const_str(cc, platform.node())


@function("current_timezone")
def _f_current_timezone(cc):
    return _const_str(cc, "UTC")


@function("assert_true")
def _f_assert_true(cc, a, msg=None):
    text = _lit_str(msg, "assert_true") if msg is not None else "assertion"
    if np.ndim(a.data) == 0 and not hasattr(a.data, "aval"):
        if not bool(a.data):
            raise ValueError(f"assert_true failed: {text}")
    return EVal(jnp.broadcast_to(jnp.asarray(True),
                                 (cc.chunk.capacity,)), a.valid, T.BOOLEAN)


# --- datetime ----------------------------------------------------------------


@function("curtime")
def _f_curtime(cc):
    import datetime as _dt

    return _const_str(cc, _dt.datetime.utcnow().strftime("%H:%M:%S"))


_alias("current_time", "curtime")
_alias("utc_time", "curtime")


@function("timestamp")
def _f_timestamp(cc, a):
    if a.type.is_string:
        from .compile import _lit_as_date_if_str

        a = _lit_as_date_if_str(a)
        if a.type.is_string:
            raise NotImplementedError(
                "timestamp() expects a datetime value/literal")
    return cc._cast(a, T.DATETIME)


@function("from_unixtime_ms")
def _f_from_unixtime_ms(cc, a):
    us = jnp.asarray(a.data, jnp.int64) * 1000
    return EVal(us, a.valid, T.DATETIME)


@function("hour_from_unixtime")
def _f_hour_from_unixtime(cc, a):
    secs = jnp.asarray(a.data, jnp.int64)
    return EVal((secs // 3600) % 24, a.valid, T.BIGINT)


@function("week_iso", scope=DATE_PART)
def _f_week_iso(cc, a):
    """ISO-8601 week number via the Thursday rule (the week containing the
    year's first Thursday is week 1)."""
    from .compile import _civil_from_days, _lit_as_date_if_str

    a = _lit_as_date_if_str(a)
    days = jnp.asarray(_as_days(a), jnp.int64)
    iso_dow = (days + 3) % 7  # 0 = Monday
    thursday = days - iso_dow + 3
    ty, _, _ = _civil_from_days(thursday)
    jan1 = _days_from_civil(ty, 1, 1)
    return EVal((thursday - jan1) // 7 + 1, a.valid, T.BIGINT)


_JODA_MAP = [("yyyy", "%Y"), ("MM", "%m"), ("dd", "%d"), ("HH", "%H"),
             ("mm", "%i"), ("ss", "%s")]


def _joda_to_mysql(p: str) -> str:
    for a, b in _JODA_MAP:
        p = p.replace(a, b)
    return p


@function("jodatime_format")
def _f_jodatime_format(cc, a, pat):
    from .compile import _FUNCTIONS

    p = _joda_to_mysql(_lit_str(pat, "jodatime_format"))
    return _FUNCTIONS["date_format"](cc, a, EVal(p, None, T.VARCHAR))


@function("str_to_jodatime")
def _f_str_to_jodatime(cc, a, pat):
    from .compile import _FUNCTIONS

    p = _joda_to_mysql(_lit_str(pat, "str_to_jodatime"))
    return _FUNCTIONS["str_to_date"](cc, a, EVal(p, None, T.VARCHAR))


@function("to_iso8601")
def _f_to_iso8601(cc, a):
    from .compile import _FUNCTIONS

    pat = "%Y-%m-%d" if a.type.kind is T.TypeKind.DATE \
        else "%Y-%m-%dT%H:%i:%s"
    return _FUNCTIONS["date_format"](cc, a, EVal(pat, None, T.VARCHAR))


# --- vector distances ---------------------------------------------------------


def _vec_pair(a, b, fn):
    from .functions_array import _arr

    la, va, ma, ea = _arr(a)
    lb, vb, mb, eb = _arr(b)
    if not (ea.is_numeric and eb.is_numeric):
        raise TypeError(f"{fn} expects numeric arrays")
    m = ma & mb
    return (jnp.where(m, jnp.asarray(va, jnp.float64), 0.0),
            jnp.where(m, jnp.asarray(vb, jnp.float64), 0.0),
            _and_valid(a.valid, b.valid))


@function("cosine_similarity")
def _f_cosine_similarity(cc, a, b):
    va, vb, valid = _vec_pair(a, b, "cosine_similarity")
    dot = jnp.sum(va * vb, axis=1)
    na = jnp.sqrt(jnp.sum(va * va, axis=1))
    nb = jnp.sqrt(jnp.sum(vb * vb, axis=1))
    denom = jnp.maximum(na * nb, 1e-300)
    return EVal(dot / denom, valid, T.DOUBLE)


@function("cosine_similarity_norm")
def _f_cosine_similarity_norm(cc, a, b):
    va, vb, valid = _vec_pair(a, b, "cosine_similarity_norm")
    return EVal(jnp.sum(va * vb, axis=1), valid, T.DOUBLE)


@function("l2_distance")
def _f_l2_distance(cc, a, b):
    va, vb, valid = _vec_pair(a, b, "l2_distance")
    d = va - vb
    return EVal(jnp.sum(d * d, axis=1), valid, T.DOUBLE)


_alias("approx_cosine_similarity", "cosine_similarity")
_alias("approx_l2_distance", "l2_distance")


# --- array builders/transforms -----------------------------------------------


def _align_array_dicts(a: EVal, b: EVal):
    """Remap two ARRAY<VARCHAR> operands onto one merged dictionary so raw
    code comparisons/concatenations mean string equality (the join-key
    _align_dict_keys contract, applied to array lanes)."""
    if not (a.type.is_array and a.type.elem.is_string
            and b.type.is_array and b.type.elem.is_string):
        return a, b
    da = a.dict or StringDict.from_values([])
    db = b.dict or StringDict.from_values([])
    if da is db:
        return a, b
    m, ra, rb = da.merge(db)

    def remap(ev, lut, old):
        d = jnp.asarray(ev.data)
        body = d[:, 1:]
        if old:
            body = jnp.asarray(lut)[jnp.clip(body, 0, old - 1)]
        out = jnp.concatenate([d[:, :1], body], axis=1)
        import dataclasses as _dc

        return _dc.replace(ev, data=out, dict=m)

    return remap(a, ra, len(da)), remap(b, rb, len(db))


def _scalar_into_dict(a: EVal, v: EVal):
    """Align a scalar string value with a string-array's dictionary;
    returns (a', v_code_eval)."""
    if not (a.type.is_array and a.type.elem.is_string):
        return a, v
    da = a.dict or StringDict.from_values([])
    if v.dict is not None and v.dict is da:
        return a, v
    vs = [str(v.data)] if isinstance(v.data, str) else None
    if vs is None and v.dict is None:
        raise NotImplementedError(
            "string-array element ops need a literal or dict-encoded value")
    dv = v.dict or StringDict.from_strings(vs)[0]
    m, ra, rb = da.merge(dv)
    import dataclasses as _dc

    d = jnp.asarray(a.data)
    body = d[:, 1:]
    if len(da):
        body = jnp.asarray(ra)[jnp.clip(body, 0, len(da) - 1)]
    a2 = _dc.replace(a, data=jnp.concatenate([d[:, :1], body], axis=1),
                     dict=m)
    if isinstance(v.data, str):
        code = m.encode_one(v.data)
        v2 = _dc.replace(v, data=jnp.asarray(max(code, 0)), dict=m)
    else:
        vcode = jnp.asarray(v.data)
        if len(dv):
            vcode = jnp.asarray(rb)[jnp.clip(vcode, 0, len(dv) - 1)]
        v2 = _dc.replace(v, data=vcode, dict=m)
    return a2, v2


def _arr_out(vals, length, elem, a_valid, dict_=None):
    k = vals.shape[1]
    data = jnp.concatenate(
        [jnp.asarray(length, vals.dtype)[:, None], vals], axis=1)
    return EVal(data, a_valid, T.ARRAY(elem), dict_)


@function("array_append")
def _f_array_append(cc, a, v):
    from .functions_array import _arr

    a, v = _scalar_into_dict(a, v)
    length, vals, mask, elem = _arr(a)
    k = vals.shape[1]
    ext = jnp.concatenate(
        [vals, jnp.zeros((vals.shape[0], 1), vals.dtype)], axis=1)
    idx = jnp.clip(length, 0, k)
    vv = jnp.broadcast_to(jnp.asarray(v.data, vals.dtype),
                          (vals.shape[0],))
    ext = ext.at[jnp.arange(vals.shape[0]), idx].set(vv)
    return _arr_out(ext, length + 1, elem, _and_valid(a.valid, v.valid),
                    a.dict)


@function("array_concat")
def _f_array_concat(cc, a, b):
    from .functions_array import _arr

    a, b = _align_array_dicts(a, b)
    la, va, ma, ea = _arr(a)
    lb, vb, mb, eb = _arr(b)
    n, ka = va.shape
    kb = vb.shape[1]
    out = jnp.zeros((n, ka + kb), va.dtype)
    out = out.at[:, :ka].set(jnp.where(ma, va, 0))
    # scatter b's live lanes right after a's length
    pos = la[:, None] + jnp.arange(kb)[None, :]
    rows = jnp.broadcast_to(jnp.arange(n)[:, None], (n, kb))
    safe = jnp.clip(pos, 0, ka + kb - 1)
    out = out.at[rows, safe].add(
        jnp.where(mb, jnp.asarray(vb, out.dtype), 0))
    return _arr_out(out, la + lb, ea, _and_valid(a.valid, b.valid), a.dict)


@function("array_remove")
def _f_array_remove(cc, a, v):
    from .functions_array import _arr

    a, v = _scalar_into_dict(a, v)
    length, vals, mask, elem = _arr(a)
    n, k = vals.shape
    vv = jnp.asarray(v.data, vals.dtype)
    keep = mask & (vals != vv)
    # stable compaction of kept lanes: dead lanes scatter out of bounds
    pos = jnp.cumsum(jnp.asarray(keep, jnp.int32), axis=1) - 1
    rows = jnp.broadcast_to(jnp.arange(n)[:, None], (n, k))
    flat_dest = jnp.where(keep, rows * k + pos, n * k)
    out = jnp.zeros((n * k,), vals.dtype).at[flat_dest.reshape(-1)].set(
        vals.reshape(-1), mode="drop").reshape(n, k)
    new_len = jnp.sum(jnp.asarray(keep, jnp.int32), axis=1)
    return _arr_out(out, new_len, elem, _and_valid(a.valid, v.valid), a.dict)


@function("array_slice")
def _f_array_slice(cc, a, off, cnt=None):
    from .functions_array import _arr

    length, vals, mask, elem = _arr(a)
    n, k = vals.shape
    o = jnp.broadcast_to(jnp.asarray(off.data, jnp.int32), (n,))
    start = jnp.where(o > 0, o - 1, length + o)  # 1-based; negative = tail
    start = jnp.clip(start, 0, length)
    cnt_v = (jnp.broadcast_to(jnp.asarray(cnt.data, jnp.int32), (n,))
             if cnt is not None else jnp.full((n,), k, jnp.int32))
    new_len = jnp.clip(jnp.minimum(cnt_v, length - start), 0, k)
    src = start[:, None] + jnp.arange(k)[None, :]
    gathered = jnp.take_along_axis(vals, jnp.clip(src, 0, k - 1), axis=1)
    lanes = jnp.arange(k)[None, :] < new_len[:, None]
    return _arr_out(jnp.where(lanes, gathered, 0), new_len, elem,
                    a.valid, a.dict)


@function("array_repeat")
def _f_array_repeat(cc, v, n_):
    k = int(n_.data)
    if k < 0:
        k = 0
    cap = cc.chunk.capacity
    elem = v.type if not v.type.is_string else T.VARCHAR
    vv = jnp.broadcast_to(jnp.asarray(v.data), (cap,))
    vals = jnp.broadcast_to(vv[:, None], (cap, max(k, 1)))
    if k == 0:
        vals = jnp.zeros((cap, 1), vv.dtype)
    length = jnp.full((cap,), k, jnp.int32)
    return _arr_out(jnp.asarray(vals), length, elem, v.valid, v.dict)


@function("array_generate")
def _f_array_generate(cc, start, stop=None, step=None):
    if stop is None:
        start, stop = EVal(1, None, T.BIGINT), start
    lo = int(start.data)
    hi = int(stop.data)
    st = int(step.data) if step is not None else (1 if hi >= lo else -1)
    if st == 0:
        raise ValueError("array_generate: step must be nonzero")
    seq = list(range(lo, hi + (1 if st > 0 else -1), st))
    cap = cc.chunk.capacity
    k = max(len(seq), 1)
    vals = jnp.broadcast_to(
        jnp.asarray(np.asarray(seq + [0] * (k - len(seq)), np.int64)),
        (cap, k))
    return _arr_out(vals, jnp.full((cap,), len(seq), jnp.int32),
                    T.BIGINT, None)


@function("array_difference")
def _f_array_difference(cc, a):
    from .functions_array import _arr

    length, vals, mask, elem = _arr(a)
    if not elem.is_numeric:
        raise TypeError("array_difference expects numeric arrays")
    v = jnp.where(mask, jnp.asarray(vals, jnp.float64 if elem.is_float
                                    else jnp.int64), 0)
    diff = jnp.concatenate(
        [jnp.zeros((v.shape[0], 1), v.dtype), v[:, 1:] - v[:, :-1]], axis=1)
    return _arr_out(jnp.where(mask, diff, 0), length,
                    T.DOUBLE if elem.is_float else T.BIGINT, a.valid)


@function("array_cum_sum")
def _f_array_cum_sum(cc, a):
    from .functions_array import _arr

    length, vals, mask, elem = _arr(a)
    if not elem.is_numeric:
        raise TypeError("array_cum_sum expects numeric arrays")
    v = jnp.where(mask, jnp.asarray(vals, jnp.float64 if elem.is_float
                                    else jnp.int64), 0)
    return _arr_out(jnp.where(mask, jnp.cumsum(v, axis=1), 0), length,
                    T.DOUBLE if elem.is_float else T.BIGINT, a.valid)


@function("array_contains_all")
def _f_array_contains_all(cc, a, b):
    from .functions_array import _arr

    a, b = _align_array_dicts(a, b)
    la, va, ma, _ = _arr(a)
    lb, vb, mb, _ = _arr(b)
    hit = (vb[:, :, None] == va[:, None, :]) & ma[:, None, :]
    found = jnp.any(hit, axis=2) | ~mb
    return EVal(jnp.all(found, axis=1), _and_valid(a.valid, b.valid),
                T.BOOLEAN)


@function("arrays_overlap")
def _f_arrays_overlap(cc, a, b):
    from .functions_array import _arr

    a, b = _align_array_dicts(a, b)
    la, va, ma, _ = _arr(a)
    lb, vb, mb, _ = _arr(b)
    hit = ((vb[:, :, None] == va[:, None, :])
           & ma[:, None, :] & mb[:, :, None])
    return EVal(jnp.any(hit, axis=(1, 2)), _and_valid(a.valid, b.valid),
                T.BOOLEAN)


@function("array_intersect")
def _f_array_intersect(cc, a, b):
    from .functions_array import _arr

    a, b = _align_array_dicts(a, b)
    la, va, ma, ea = _arr(a)
    lb, vb, mb, _ = _arr(b)
    n, k = va.shape
    in_b = jnp.any((va[:, :, None] == vb[:, None, :]) & mb[:, None, :],
                   axis=2)
    first = (jnp.cumsum(
        jnp.asarray((va[:, :, None] == va[:, None, :])
                    & ma[:, None, :], jnp.int32), axis=2
    ).diagonal(axis1=1, axis2=2) == 1)  # first occurrence lanes
    keep = ma & in_b & first
    pos = jnp.cumsum(jnp.asarray(keep, jnp.int32), axis=1) - 1
    rows = jnp.broadcast_to(jnp.arange(n)[:, None], (n, k))
    flat_dest = jnp.where(keep, rows * k + pos, n * k)  # dead lanes drop
    out = jnp.zeros((n * k,), va.dtype).at[flat_dest.reshape(-1)].set(
        va.reshape(-1), mode="drop").reshape(n, k)
    return _arr_out(out, jnp.sum(jnp.asarray(keep, jnp.int32), axis=1),
                    ea, _and_valid(a.valid, b.valid), a.dict)


# --- JSON ---------------------------------------------------------------------

_alias("get_json_object", "get_json_string")
_alias("json_query", "get_json_string")
_alias("json_string", "get_json_string")


def _json_try(s):
    import json as _json

    try:
        return _json.loads(str(s))
    except Exception:  # noqa: BLE001
        return None


@function("json_length")
def _f_json_length(cc, a, path=None):
    from .functions_wave3 import _json_get

    p = _lit_str(path, "json_length") if path is not None else None

    def f(s):
        v = _json_get(s, p) if p else _json_try(s)
        if isinstance(v, (dict, list)):
            return len(v)
        return 1 if v is not None else 0

    return _string_int_fn(cc, a, f, T.BIGINT)


@function("json_keys")
def _f_json_keys(cc, a, path=None):
    import json as _json

    from .functions_wave3 import _json_get

    p = _lit_str(path, "json_keys") if path is not None else None

    def f(s):
        v = _json_get(s, p) if p else _json_try(s)
        if isinstance(v, dict):
            return _json.dumps(sorted(v.keys()), separators=(",", ":"))
        return ""

    return _string_map_fn(cc, a, f)


@function("json_exists")
def _f_json_exists(cc, a, path):
    from .functions_wave3 import _json_get

    p = _lit_str(path, "json_exists")
    return _string_bool_fn(cc, a, lambda s: _json_get(s, p) is not None)


@function("is_json_scalar")
def _f_is_json_scalar(cc, a):
    return _string_bool_fn(
        cc, a, lambda s: not isinstance(_json_try(s), (dict, list))
        and _json_try(s) is not None)


@function("json_pretty")
def _f_json_pretty(cc, a):
    import json as _json

    def f(s):
        v = _json_try(s)
        return _json.dumps(v, indent=2) if v is not None else ""

    return _string_map_fn(cc, a, f)


@function("parse_json")
def _f_parse_json(cc, a):
    """VARCHAR already IS the json representation in this engine."""
    return a


_alias("to_json", "parse_json")


@function("get_json_bool")
def _f_get_json_bool(cc, a, path):
    from .functions_wave3 import _json_get

    p = _lit_str(path, "get_json_bool")

    def f(s):
        v = _json_get(s, p)
        return bool(v) if isinstance(v, (bool, int, float)) else False

    return _string_bool_fn(cc, a, f)


@function("json_contains")
def _f_json_contains(cc, a, needle):
    target = _json_try(_lit_str(needle, "json_contains"))

    def f(s):
        v = _json_try(s)
        if isinstance(v, list):
            return target in v
        if isinstance(v, dict) and isinstance(target, dict):
            return all(v.get(k) == tv for k, tv in target.items())
        return v == target

    return _string_bool_fn(cc, a, f)


# --- bitmap manipulation -------------------------------------------------------


def _planes(a, fn):
    if not a.type.is_bitmap:
        raise TypeError(f"{fn} expects a BITMAP, got {a.type!r}")
    return jnp.asarray(a.data), a.type.precision


@function("bitmap_empty")
def _f_bitmap_empty(cc):
    from ..runtime.config import config

    nbits = config.get("bitmap_default_domain")
    cap = cc.chunk.capacity
    return EVal(jnp.zeros((cap, (nbits + 7) // 8), jnp.int8), None,
                T.BITMAP(nbits))


@function("bitmap_from_string")
def _f_bitmap_from_string(cc, a):
    """'1,3,5' -> bitmap (per-dictionary-value parse, planes LUT)."""
    from ..runtime.config import config

    nbits = config.get("bitmap_default_domain")
    w8 = (nbits + 7) // 8
    if a.dict is None and isinstance(a.data, str):
        row = np.zeros(w8, np.uint8)
        for tok in a.data.split(","):
            tok = tok.strip()
            if tok.isdigit() and int(tok) < nbits:
                v = int(tok)
                row[v >> 3] |= 1 << (v & 7)
        planes = jnp.broadcast_to(jnp.asarray(row.view(np.int8)),
                                  (cc.chunk.capacity, w8))
        return EVal(planes, a.valid, T.BITMAP(nbits))
    assert a.dict is not None, "bitmap_from_string needs a string column"
    nd = max(len(a.dict), 1)
    lut = np.zeros((nd, w8), np.uint8)
    for i in range(len(a.dict)):
        for tok in str(a.dict.values[i]).split(","):
            tok = tok.strip()
            if tok.isdigit() and int(tok) < nbits:
                v = int(tok)
                lut[i, v >> 3] |= 1 << (v & 7)
    planes = jnp.asarray(lut.view(np.int8))[
        jnp.clip(jnp.asarray(a.data, jnp.int32), 0, nd - 1)]
    return EVal(planes, a.valid, T.BITMAP(nbits))


def _bit_positions(planes):
    from ..ops.sketch import _unpack_bits

    bits = _unpack_bits(planes)  # [cap, nbits]
    return bits, jnp.arange(bits.shape[1], dtype=jnp.int64)


@function("bitmap_min")
def _f_bitmap_min(cc, a):
    planes, nbits = _planes(a, "bitmap_min")
    bits, pos = _bit_positions(planes)
    big = jnp.asarray(1 << 62, jnp.int64)
    mn = jnp.min(jnp.where(bits == 1, pos, big), axis=1)
    empty = mn == big
    return EVal(jnp.where(empty, 0, mn),
                _and_valid(a.valid, ~empty), T.BIGINT)


@function("bitmap_max")
def _f_bitmap_max(cc, a):
    planes, nbits = _planes(a, "bitmap_max")
    bits, pos = _bit_positions(planes)
    mx = jnp.max(jnp.where(bits == 1, pos, -1), axis=1)
    empty = mx < 0
    return EVal(jnp.where(empty, 0, mx),
                _and_valid(a.valid, ~empty), T.BIGINT)


@function("bitmap_remove")
def _f_bitmap_remove(cc, a, v):
    planes, nbits = _planes(a, "bitmap_remove")
    cap = planes.shape[0]
    vv = jnp.broadcast_to(jnp.asarray(v.data, jnp.int64), (cap,))
    byte = jnp.clip(jnp.asarray(vv >> 3, jnp.int32), 0,
                    planes.shape[1] - 1)
    bit = jnp.asarray(vv & 7, jnp.int32)
    in_range = (vv >= 0) & (vv < nbits)
    clear = jnp.where(
        jnp.arange(planes.shape[1])[None, :] == byte[:, None],
        (1 << bit)[:, None], 0)
    u = (jnp.asarray(planes, jnp.int32) & 0xFF) & ~jnp.where(
        in_range[:, None], clear, 0)
    return EVal(jnp.asarray(u, jnp.int8), a.valid, a.type)


@function("bitmap_has_any")
def _f_bitmap_has_any(cc, a, b):
    from ..ops import sketch

    return EVal(sketch.bitmap_count(
        sketch.bitmap_binary(a.data, b.data, "and")) > 0,
        _and_valid(a.valid, b.valid), T.BOOLEAN)


@function("sub_bitmap")
def _f_sub_bitmap(cc, a, off, cnt):
    """Range mask: keep set bits by POSITION range [off, off+cnt)."""
    planes, nbits = _planes(a, "sub_bitmap")
    bits, pos = _bit_positions(planes)
    rank = jnp.cumsum(jnp.asarray(bits, jnp.int32), axis=1) - bits
    o = int(off.data)
    c = int(cnt.data)
    keep = (bits == 1) & (rank >= o) & (rank < o + c)
    from ..ops.sketch import _pack_bits

    return EVal(_pack_bits(jnp.asarray(keep, jnp.int8)), a.valid, a.type)


@function("bitmap_subset_in_range")
def _f_bitmap_subset_in_range(cc, a, lo, hi):
    planes, nbits = _planes(a, "bitmap_subset_in_range")
    bits, pos = _bit_positions(planes)
    keep = (bits == 1) & (pos[None, :] >= int(lo.data)) \
        & (pos[None, :] < int(hi.data))
    from ..ops.sketch import _pack_bits

    return EVal(_pack_bits(jnp.asarray(keep, jnp.int8)), a.valid, a.type)


@function("bitmap_subset_limit")
def _f_bitmap_subset_limit(cc, a, start, lim):
    planes, nbits = _planes(a, "bitmap_subset_limit")
    bits, pos = _bit_positions(planes)
    ge = (bits == 1) & (pos[None, :] >= int(start.data))
    rank = jnp.cumsum(jnp.asarray(ge, jnp.int32), axis=1) - ge
    keep = ge & (rank < int(lim.data))
    from ..ops.sketch import _pack_bits

    return EVal(_pack_bits(jnp.asarray(keep, jnp.int8)), a.valid, a.type)


@function("bitmap_hash")
def _f_bitmap_hash(cc, a):
    """to_bitmap(hash(x) % domain) (reference: bitmap_hash on varchar)."""
    from ..ops import sketch
    from ..ops.aggregate import _hash_input_i64
    from ..ops.common import mix64
    from ..runtime.config import config

    nbits = config.get("bitmap_default_domain")
    cap = cc.chunk.capacity
    h = mix64(jnp.broadcast_to(_hash_input_i64(a), (cap,)))
    v = jnp.asarray(h % jnp.uint64(nbits), jnp.int64)
    valid = (jnp.ones((cap,), jnp.bool_) if a.valid is None
             else jnp.broadcast_to(a.valid, (cap,)))
    return EVal(sketch.bitmap_from_values(v, valid, nbits), None,
                T.BITMAP(nbits))


_alias("bitmap_hash64", "bitmap_hash")


@function("array_to_bitmap")
def _f_array_to_bitmap(cc, a):
    from .functions_array import _arr
    from ..ops.sketch import _pack_bits
    from ..runtime.config import config

    length, vals, mask, elem = _arr(a)
    if not elem.is_integer:
        raise TypeError("array_to_bitmap expects integer arrays")
    nbits = config.get("bitmap_default_domain")
    v = jnp.asarray(vals, jnp.int64)
    ok = mask & (v >= 0) & (v < nbits)
    hit = jnp.any(
        (jnp.arange(nbits)[None, None, :] == v[:, :, None]) & ok[:, :, None],
        axis=1)
    return EVal(_pack_bits(jnp.asarray(hit, jnp.int8)), a.valid,
                T.BITMAP(nbits))


@function("bitmap_to_array")
def _f_bitmap_to_array(cc, a):
    planes, nbits = _planes(a, "bitmap_to_array")
    if nbits > 4096:
        raise NotImplementedError(
            "bitmap_to_array is gated to domains <= 4096 bits "
            "(the array lane width is the domain)")
    bits, pos = _bit_positions(planes)
    n, k = bits.shape
    keep = bits == 1
    rank = jnp.cumsum(jnp.asarray(keep, jnp.int32), axis=1) - keep
    rows = jnp.broadcast_to(jnp.arange(n)[:, None], (n, k))
    flat_dest = jnp.where(keep, rows * k + rank, n * k)  # dead lanes drop
    out = jnp.zeros((n * k,), jnp.int64).at[flat_dest.reshape(-1)].set(
        jnp.broadcast_to(pos[None, :], (n, k)).reshape(-1),
        mode="drop").reshape(n, k)
    length = jnp.sum(jnp.asarray(keep, jnp.int32), axis=1)
    data = jnp.concatenate([jnp.asarray(length, jnp.int64)[:, None], out],
                           axis=1)
    return EVal(data, a.valid, T.ARRAY(T.BIGINT))


# --- HLL serde ----------------------------------------------------------------


@function("hll_serialize")
def _f_hll_serialize(cc, a):
    """Registers ARE the serialized form (dense fixed-width sketches)."""
    if not a.type.is_hll:
        raise TypeError("hll_serialize expects an HLL value")
    return a


_alias("hll_deserialize", "hll_serialize")


# --- regexp long tail ---------------------------------------------------------


@function("regexp_count")
def _f_regexp_count(cc, a, pat):
    import re as _re

    rx = _re.compile(_lit_str(pat, "regexp_count"))
    return _string_int_fn(cc, a, lambda s: len(rx.findall(str(s))),
                          T.BIGINT)


@function("regexp_position")
def _f_regexp_position(cc, a, pat):
    import re as _re

    rx = _re.compile(_lit_str(pat, "regexp_position"))

    def f(s):
        m = rx.search(str(s))
        return (m.start() + 1) if m else -1  # 1-based; -1 = no match

    return _string_int_fn(cc, a, f, T.BIGINT)


@function("regexp_split")
def _f_regexp_split(cc, a, pat):
    import re as _re

    rx = _re.compile(_lit_str(pat, "regexp_split"))
    return _string_to_array_fn(cc, a, lambda s: rx.split(str(s)))


@function("regexp_extract_all")
def _f_regexp_extract_all(cc, a, pat, group=None):
    import re as _re

    rx = _re.compile(_lit_str(pat, "regexp_extract_all"))
    g = int(group.data) if group is not None else (
        1 if rx.groups else 0)

    def f(s):
        out = []
        for m in rx.finditer(str(s)):
            out.append(m.group(g) or "")
        return out

    return _string_to_array_fn(cc, a, f)


# --- numeric / utility long tail ----------------------------------------------


@function("equiwidth_bucket")
def _f_equiwidth_bucket(cc, x, lo, hi, nb):
    """Bucket id in [0, nb+1]: 0 below lo, nb+1 at/above hi (reference:
    the histogram bucketing builtin)."""
    xv = jnp.asarray(x.data, jnp.float64)
    lo_v, hi_v, n = float(lo.data), float(hi.data), int(nb.data)
    if hi_v <= lo_v or n <= 0:
        raise ValueError("equiwidth_bucket needs lo < hi and buckets > 0")
    b = jnp.floor((xv - lo_v) / (hi_v - lo_v) * n) + 1
    b = jnp.where(xv < lo_v, 0, jnp.where(xv >= hi_v, n + 1, b))
    return EVal(jnp.asarray(b, jnp.int64), x.valid, T.BIGINT)


@function("bit_shift_right_logical")
def _f_bsr_logical(cc, a, n):
    av = jnp.asarray(a.data, jnp.int64).view(jnp.uint64)
    nv = jnp.asarray(n.data, jnp.uint64)
    return EVal(jnp.asarray(av >> nv, jnp.uint64).view(jnp.int64),
                _and_valid(a.valid, n.valid), T.BIGINT)


@function("sec_to_time")
def _f_sec_to_time(cc, a):
    def f(v):
        v = int(v)
        sign = "-" if v < 0 else ""
        v = abs(v)
        return f"{sign}{v // 3600:02d}:{(v // 60) % 60:02d}:{v % 60:02d}"

    return _bounded_value_strings(cc, a, f, "sec_to_time")


@function("bar")
def _f_bar(cc, x, lo, hi, width):
    """Text histogram bar (reference: the diagnostics bar() render)."""
    lo_v, hi_v, w = float(lo.data), float(hi.data), int(width.data)

    def f(v):
        frac = (float(v) - lo_v) / max(hi_v - lo_v, 1e-300)
        n = max(0, min(w, int(round(frac * w))))
        return "█" * n

    return _bounded_value_strings(cc, x, f, "bar")


@function("query_id")
def _f_query_id(cc):
    return _const_str(cc, "")  # per-statement ids live in the query log


_alias("last_query_id", "query_id")


@function("sleep")
def _f_sleep(cc, a):
    import time as _time

    _time.sleep(min(float(a.data), 5.0))  # capped trace-time sleep
    return EVal(jnp.broadcast_to(jnp.asarray(True),
                                 (cc.chunk.capacity,)), None, T.BOOLEAN)
