"""TPC-H tables from a seed: the benchmark's own copy of the generator.

The yardstick may not move when the program does, so the data every cell
runs on is made here and not by `starrocks_tpu/storage/datagen/tpch.py`.
This file draws the same random numbers in the same order as that
generator (one `default_rng(seed)` stream), so for a given (SF, seed) it
gives the same tables, bit for bit — `tests/test_harness.py` holds it to
that on CPU. What differs is how the string columns are built: the
original formats one Python string per row and hands the list to
`HostTable.from_pydict`, which dictionary-encodes it with `np.unique` over
objects (o_clerk, o_orderpriority, o_comment: 15M rows each at SF10 — most of
its 111 s there). Here every string column is a small sorted dictionary plus
an int32 code array computed with numpy, which is what the original's result
holds anyway.

Schema, row counts, key ranges and value distributions are the original's:
supplier 10k*SF, customer 150k*SF, part 200k*SF, partsupp 800k*SF, orders
1.5M*SF, lineitem about 6M*SF (1..7 lines per order), uniform keys, money as
DECIMAL(15,2), dates as DATE.
"""

from __future__ import annotations

import datetime
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from starrocks_tpu import types as T
from starrocks_tpu.column import Field, HostTable, Schema, StringDict

_EPOCH = datetime.date(1970, 1, 1)


def _days(y, m, d):
    return (datetime.date(y, m, d) - _EPOCH).days


START_DATE = _days(1992, 1, 1)
END_DATE = _days(1998, 8, 2)

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
SHIPINSTRUCT = ["COLLECT COD", "DELIVER IN PERSON", "NONE", "TAKE BACK RETURN"]
TYPES_SYL1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPES_SYL2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPES_SYL3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
CONTAINERS_SYL1 = ["SM", "LG", "MED", "JUMBO", "WRAP"]
CONTAINERS_SYL2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]
P_NAME_WORDS = [
    "almond", "antique", "aquamarine", "azure", "beige", "bisque", "black",
    "blanched", "blue", "blush", "brown", "burlywood", "burnished", "chartreuse",
    "chiffon", "chocolate", "coral", "cornflower", "cornsilk", "cream", "cyan",
    "dark", "deep", "dim", "dodger", "drab", "firebrick", "floral", "forest",
    "frosted", "gainsboro", "ghost", "goldenrod", "green", "grey", "honeydew",
    "hot", "indian", "ivory", "khaki", "lace", "lavender", "lawn", "lemon",
    "light", "lime", "linen", "magenta", "maroon", "medium", "metallic",
    "midnight", "mint", "misty", "moccasin", "navajo", "navy", "olive",
]

DEC = T.DECIMAL(15, 2)

# the deployment's DDL: unique keys and bucketing keys per table, as the
# program's `storage/catalog.py` registers them for TPC-H
UNIQUE_KEYS = {
    "region": [("r_regionkey",)],
    "nation": [("n_nationkey",)],
    "supplier": [("s_suppkey",)],
    "customer": [("c_custkey",)],
    "part": [("p_partkey",)],
    "partsupp": [("ps_partkey", "ps_suppkey")],
    "orders": [("o_orderkey",)],
    "lineitem": [("l_orderkey", "l_linenumber")],
}
DISTRIBUTION = {
    "lineitem": ("l_orderkey",),
    "orders": ("o_orderkey",),
    "customer": ("c_custkey",),
    "part": ("p_partkey",),
    "partsupp": ("ps_partkey",),
    "supplier": ("s_suppkey",),
}


def _ht(cols: dict, types: dict) -> HostTable:
    return HostTable.from_pydict(cols, types=types)


def _const(value: str, n: int):
    return StringDict.from_values([value]), np.zeros(n, dtype=np.int32)


def _numbered(prefix: str, keys: np.ndarray):
    """`f"{prefix}{k:09d}"` for ascending unique keys: the formatted strings
    are already sorted, so the codes are 0..n-1."""
    vals = np.char.add(prefix, np.char.zfill(keys.astype(str), 9))
    return (StringDict.from_values(vals.astype(object)),
            np.arange(len(keys), dtype=np.int32))


def _combo(parts: list, draws: list, sep: str = " "):
    """Column of `sep.join(parts[i][draws[i][row]])`: dictionary of every
    combination, sorted, and per-row codes through a look-up table."""
    shape = tuple(len(p) for p in parts)
    grid = np.indices(shape).reshape(len(parts), -1)
    combos = [sep.join(p[i] for p, i in zip(parts, idx)) for idx in grid.T]
    vals = sorted(set(combos))
    index = {v: i for i, v in enumerate(vals)}
    lut = np.array([index[c] for c in combos], dtype=np.int32).reshape(shape)
    return StringDict.from_values(vals), lut[tuple(draws)]


def _present(values: np.ndarray, strings: list):
    """What `StringDict.from_strings([strings[v] for v in values])` gives for
    `strings` sorted ascending: only the strings that occur, and codes into
    them."""
    present = np.unique(values)
    return (StringDict.from_values([strings[i] for i in present]),
            np.searchsorted(present, values).astype(np.int32))


def generate(sf: float, seed: int) -> dict:
    """All eight tables as HostTables keyed by lowercase name."""
    rng = np.random.default_rng(seed)
    out = {}

    out["region"] = _ht(
        {"r_regionkey": np.arange(5, dtype=np.int32), "r_name": REGIONS,
         "r_comment": ["" for _ in REGIONS]},
        {"r_regionkey": T.INT},
    )
    out["nation"] = _ht(
        {
            "n_nationkey": np.arange(25, dtype=np.int32),
            "n_name": [n for n, _ in NATIONS],
            "n_regionkey": np.array([r for _, r in NATIONS], dtype=np.int32),
            "n_comment": ["" for _ in NATIONS],
        },
        {"n_nationkey": T.INT, "n_regionkey": T.INT},
    )

    ns = max(int(10_000 * sf), 10)
    s_key = np.arange(1, ns + 1, dtype=np.int64)
    s_nation = rng.integers(0, 25, ns).astype(np.int32)
    out["supplier"] = _ht(
        {
            "s_suppkey": s_key,
            "s_name": _numbered("Supplier#", s_key),
            "s_address": _const("", ns),
            "s_nationkey": s_nation,
            "s_phone": _const("", ns),
            "s_acctbal": np.round(rng.uniform(-999.99, 9999.99, ns), 2),
            "s_comment": _const("", ns),
        },
        {"s_suppkey": T.BIGINT, "s_nationkey": T.INT, "s_acctbal": DEC},
    )

    nc = max(int(150_000 * sf), 30)
    c_key = np.arange(1, nc + 1, dtype=np.int64)
    out["customer"] = _ht(
        {
            "c_custkey": c_key,
            "c_name": _numbered("Customer#", c_key),
            "c_address": _const("", nc),
            "c_nationkey": rng.integers(0, 25, nc).astype(np.int32),
            "c_phone": _const("", nc),
            "c_acctbal": np.round(rng.uniform(-999.99, 9999.99, nc), 2),
            "c_mktsegment": (StringDict.from_values(sorted(SEGMENTS)),
                             rng.integers(0, 5, nc).astype(np.int32)),
            "c_comment": _const("", nc),
        },
        {"c_custkey": T.BIGINT, "c_nationkey": T.INT, "c_acctbal": DEC},
    )

    npart = max(int(200_000 * sf), 40)
    p_key = np.arange(1, npart + 1, dtype=np.int64)
    brand_m = rng.integers(1, 6, npart)
    brand_n = rng.integers(1, 6, npart)
    t1 = rng.integers(0, len(TYPES_SYL1), npart)
    t2 = rng.integers(0, len(TYPES_SYL2), npart)
    t3 = rng.integers(0, len(TYPES_SYL3), npart)
    ct1 = rng.integers(0, len(CONTAINERS_SYL1), npart)
    ct2 = rng.integers(0, len(CONTAINERS_SYL2), npart)
    retail = np.round(900 + (p_key % 1000) / 10 + 100 * (p_key % 10), 2)
    digits = [str(d) for d in range(1, 6)]
    nw = len(P_NAME_WORDS)
    out["part"] = _ht(
        {
            "p_partkey": p_key,
            # a few colour words; Q9/Q20 filter on LIKE '%green%'
            "p_name": _combo([P_NAME_WORDS, P_NAME_WORDS],
                             [(p_key * 7) % nw, (p_key * 13 + 3) % nw]),
            "p_mfgr": (StringDict.from_values(
                [f"Manufacturer#{m}" for m in range(1, 6)]),
                (brand_m - 1).astype(np.int32)),
            "p_brand": _combo([["Brand#"], digits, digits],
                              [np.zeros(npart, dtype=np.int64),
                               brand_m - 1, brand_n - 1], sep=""),
            "p_type": _combo([TYPES_SYL1, TYPES_SYL2, TYPES_SYL3],
                             [t1, t2, t3]),
            "p_size": rng.integers(1, 51, npart).astype(np.int32),
            "p_container": _combo([CONTAINERS_SYL1, CONTAINERS_SYL2],
                                  [ct1, ct2]),
            "p_retailprice": retail,
            "p_comment": _const("", npart),
        },
        {"p_partkey": T.BIGINT, "p_size": T.INT, "p_retailprice": DEC},
    )

    # partsupp: 4 suppliers per part (TPC-H rule); supplier j of part p is
    # (p + j*(ns/4 + p//ns)) % ns + 1, the spec-like spread lineitem reuses
    ps_part = np.repeat(p_key, 4)
    j = np.tile(np.arange(4), npart)
    ps_supp = ((ps_part - 1 + j * (ns // 4 + (ps_part - 1) // ns)) % ns + 1
               ).astype(np.int64)
    out["partsupp"] = _ht(
        {
            "ps_partkey": ps_part,
            "ps_suppkey": ps_supp,
            "ps_availqty": rng.integers(1, 10_000, npart * 4).astype(np.int32),
            "ps_supplycost": np.round(rng.uniform(1.0, 1000.0, npart * 4), 2),
            "ps_comment": _const("", npart * 4),
        },
        {"ps_partkey": T.BIGINT, "ps_suppkey": T.BIGINT,
         "ps_availqty": T.INT, "ps_supplycost": DEC},
    )

    # orders and lineitem are nearly all of the rows. Their random draws are
    # made on this thread in the original's order; each column is built from
    # its draws on a worker thread as soon as they exist (numpy releases the
    # interpreter lock), as the scaled integers and codes a HostTable holds,
    # without the original's detour through float64 and `from_pydict`, and
    # every draw is dropped as soon as its column is built: on a fresh
    # machine touching new memory costs more than the arithmetic.
    no = max(int(1_500_000 * sf), 150)
    o_key = np.arange(1, no + 1, dtype=np.int64)
    retail_cents = np.round(retail * 100).astype(np.int64)
    cutoff = _days(1995, 6, 17)
    li: dict = {}
    pool = ThreadPoolExecutor(max_workers=2)
    jobs = []

    def later(fn, *draws):
        jobs.append(pool.submit(fn, *draws))

    o_cust = rng.integers(1, nc + 1, no)
    o_date = rng.integers(START_DATE, END_DATE - 151, no).astype(np.int32)
    o_prio = rng.integers(0, 5, no)
    nlines = rng.integers(1, 8, no)  # 1..7 lines per order
    nl = int(nlines.sum())
    l_order = np.repeat(o_key, nlines)
    li["l_orderkey"] = l_order

    def linenumber():
        li["l_linenumber"] = (
            np.arange(nl) - np.repeat(np.cumsum(nlines) - nlines, nlines) + 1
        ).astype(np.int32)
        li["l_comment"] = np.zeros(nl, dtype=np.int32)

    later(linenumber)
    l_part = rng.integers(1, npart + 1, nl)
    li["l_partkey"] = l_part

    def suppkey(lj):
        li["l_suppkey"] = (l_part - 1 + lj * (ns // 4 + (l_part - 1) // ns)) % ns + 1

    later(suppkey, rng.integers(0, 4, nl))
    l_qty = rng.integers(1, 51, nl)
    l_disc = rng.integers(0, 11, nl)  # hundredths, as DECIMAL(15,2) holds them
    l_tax = rng.integers(0, 9, nl)
    li["l_discount"], li["l_tax"] = l_disc, l_tax

    def money():
        # order total = sum of its lines' gross prices, in float64 and added
        # in row order, as the original's np.add.at does
        cents = l_qty * retail_cents[l_part - 1]
        li["l_extendedprice"] = cents
        li["l_quantity"] = l_qty * 100
        gross = cents / 100.0
        gross *= 1 - l_disc / 100.0
        gross *= 1 + l_tax / 100.0
        sums = np.bincount(l_order - 1, weights=np.round(gross, 2), minlength=no)
        return np.round(np.round(sums, 2) * 100).astype(np.int64)

    o_total = pool.submit(money)
    l_odate = np.repeat(o_date, nlines)

    def shipdate(days):
        ship = (l_odate + days).astype(np.int32)
        li["l_shipdate"] = ship
        li["l_linestatus"] = (ship > cutoff).astype(np.int32)  # F=0 else O=1

    def commitdate(days):
        li["l_commitdate"] = (l_odate + days).astype(np.int32)

    def receiptdate(days, coin):
        receipt = (li["l_shipdate"] + days).astype(np.int32)
        li["l_receiptdate"] = receipt
        # R or A by a coin where received by the cut-off, else N; codes into
        # the sorted dictionary [A, N, R]
        li["l_returnflag"] = np.array([0, 2, 1], dtype=np.int32)[
            np.where(receipt <= cutoff, coin, 2)]

    def code(name, draw):
        li[name] = draw.astype(np.int32)

    ship_job = pool.submit(shipdate, rng.integers(1, 122, nl))
    later(commitdate, rng.integers(30, 91, nl))
    receipt_days = rng.integers(1, 31, nl)
    ship_job.result()
    later(receiptdate, receipt_days, rng.integers(0, 2, nl))
    del receipt_days
    later(code, "l_shipinstruct", rng.integers(0, 4, nl))
    later(code, "l_shipmode", rng.integers(0, 7, nl))
    o_status = rng.integers(0, 3, no)
    o_total = o_total.result()
    for job in jobs:
        job.result()
    pool.shutdown()

    def varchar(name, values):
        return Field(name, T.VARCHAR, True, StringDict.from_values(values))

    out["lineitem"] = HostTable(Schema((
        Field("l_orderkey", T.BIGINT), Field("l_partkey", T.BIGINT),
        Field("l_suppkey", T.BIGINT), Field("l_linenumber", T.INT),
        Field("l_quantity", DEC), Field("l_extendedprice", DEC),
        Field("l_discount", DEC), Field("l_tax", DEC),
        varchar("l_returnflag", ["A", "N", "R"]),
        varchar("l_linestatus", ["F", "O"]),
        Field("l_shipdate", T.DATE), Field("l_commitdate", T.DATE),
        Field("l_receiptdate", T.DATE),
        varchar("l_shipinstruct", sorted(SHIPINSTRUCT)),
        varchar("l_shipmode", sorted(SHIPMODES)),
        varchar("l_comment", [""]),
    )), li)

    prio_dict, prio_codes = _present(o_prio, PRIORITIES)
    clerk_dict, clerk_codes = _present(
        o_key % 1000, [f"Clerk#{k:09d}" for k in range(1000)])
    out["orders"] = HostTable(Schema((
        Field("o_orderkey", T.BIGINT), Field("o_custkey", T.BIGINT),
        varchar("o_orderstatus", ["F", "O"]), Field("o_totalprice", DEC),
        Field("o_orderdate", T.DATE),
        Field("o_orderpriority", T.VARCHAR, True, prio_dict),
        Field("o_clerk", T.VARCHAR, True, clerk_dict),
        Field("o_shippriority", T.INT), varchar("o_comment", [""]),
    )), {
        "o_orderkey": o_key, "o_custkey": o_cust,
        "o_orderstatus": (o_status != 0).astype(np.int32),
        "o_totalprice": o_total, "o_orderdate": o_date,
        "o_orderpriority": prio_codes, "o_clerk": clerk_codes,
        "o_shippriority": np.zeros(no, dtype=np.int32),
        "o_comment": np.zeros(no, dtype=np.int32),
    })
    return out
