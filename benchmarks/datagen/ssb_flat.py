"""One backend's share of the Star Schema Benchmark's `lineorder_flat`, from a
seed.

The deployment (StarRocks documentation, "SSB Flat-table Benchmarking") joins
`lineorder` with `customer`, `supplier` and `part` once, at load, into one wide
table, `DISTRIBUTED BY HASH(LO_ORDERKEY)`: a backend holds the orders whose key
hashes to it, with every line of each. `generate(sf, seed)` makes the share of
ONE of eight backends at scale factor `sf`: 187,500 x sf orders of the 1.5M x
sf, one to seven lines each, so about 750,000 x sf rows of the 6M x sf (75.0M
of 600M at SF100). The value domains are the whole benchmark's at `sf`
(customers 30,000 x sf, suppliers 2,000 x sf, parts 200,000 x floor(1 + log2
sf), order keys up to 6M x sf), not an eighth of them.

The columns, their order, their names in capitals and their types are the
documentation's DDL. Values follow the SSB specification (O'Neil et al.,
"Star Schema Benchmark", rev. 3) and `ssb-dbgen` where this file's writer
could recall them; `benchmarks/configs/ssb_flat_sf100_share.json` lists under
`assumed` what could not be checked:

    LO_ORDERKEY       dbgen's sparse keys (8 used of each 32); this backend's
                      are every eighth order of the sequence
    LO_ORDERDATE      uniform over 1992-01-01 .. 1998-08-02
    LO_QUANTITY 1..50, LO_DISCOUNT 0..10, LO_TAX 0..8
    LO_EXTENDEDPRICE  quantity x the part's retail price in cents
                      (90000 + (partkey / 10) % 20001 + 100 x (partkey % 1000))
    LO_REVENUE        extendedprice x (100 - discount) / 100, in integers
    LO_SUPPLYCOST     6 x retail price / 10
    LO_ORDTOTALPRICE  the order's sum of extendedprice x (100 - discount) / 100
                      x (100 + tax) / 100
    C_CITY / S_CITY   the nation's first nine characters, padded, and a digit:
                      250 cities; city -> nation -> region
    P_BRAND           category and 1..40 (`MFGR#2221`): 1,000 brands;
                      brand -> category (`MFGR#22`) -> manufacturer (`MFGR#2`)

Rows lie in the order of the table's `DUPLICATE KEY(LO_ORDERDATE,
LO_ORDERKEY)`. Text no statement of the benchmark reads and whose values are
one per dimension row (names, addresses, phones, P_NAME) is one empty string.
Every random draw comes from a stream of its own, spawned from the seed in a
fixed order, so the tables do not depend on how the work is spread over
threads.
"""

from __future__ import annotations

import datetime
import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from starrocks_tpu import types as T
from starrocks_tpu.column import Field, HostTable, Schema, StringDict

BACKENDS = 8  # a v5e-8 stands for upstream's cluster; this is backend 0's share
ORDERS_PER_SF = 1_500_000

_EPOCH = datetime.date(1970, 1, 1)
START_DATE = (datetime.date(1992, 1, 1) - _EPOCH).days
END_DATE = (datetime.date(1998, 8, 2) - _EPOCH).days

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
CITIES = [f"{name[:9]:<9}{d}" for name, _ in NATIONS for d in range(10)]
MFGRS = [f"MFGR#{m}" for m in range(1, 6)]
CATEGORIES = [f"{m}{c}" for m in MFGRS for c in range(1, 6)]
BRANDS = [f"{c}{b}" for c in CATEGORIES for b in range(1, 41)]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
COLORS = [
    "almond", "antique", "aquamarine", "azure", "beige", "bisque", "black",
    "blanched", "blue", "blush", "brown", "burlywood", "burnished",
    "chartreuse", "chiffon", "chocolate", "coral", "cornflower", "cornsilk",
    "cream", "cyan", "dark", "deep", "dim", "dodger", "drab", "firebrick",
    "floral", "forest", "frosted", "gainsboro", "ghost", "goldenrod", "green",
    "grey", "honeydew", "hot", "indian", "ivory", "khaki", "lace", "lavender",
    "lawn", "lemon", "light", "lime", "linen", "magenta", "maroon", "medium",
    "metallic", "midnight", "mint", "misty", "moccasin", "navajo", "navy",
    "olive", "orange", "orchid", "pale", "papaya", "peach", "peru", "pink",
    "plum", "powder", "puff", "purple", "red", "rose", "rosy", "royal",
    "saddle", "salmon", "sandy", "seashell", "sienna", "sky", "slate", "smoke",
    "snow", "spring", "steel", "tan", "thistle", "tomato", "turquoise",
    "violet", "wheat", "white", "yellow",
]
TYPES = [f"{a} {b} {c}"
         for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
         for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
         for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")]
CONTAINERS = [f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
              for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM")]

# the deployment's DDL: a duplicate-key table (no unique key), bucketed by
# the order key
UNIQUE_KEYS: dict = {}
DISTRIBUTION = {"lineorder_flat": ("LO_ORDERKEY",)}


def _coded(values: list):
    """(sorted dictionary, look-up table from a value's position in `values`
    to its code): dictionary codes order as the strings do."""
    order = sorted(values)
    index = {v: i for i, v in enumerate(order)}
    return (StringDict.from_values(order),
            np.array([index[v] for v in values], dtype=np.int32))


def sizes(sf: float) -> dict:
    """Rows of the dimensions the flat table was joined from, and this
    backend's orders."""
    return {"orders": max(int(round(ORDERS_PER_SF * sf / BACKENDS)), 100),
            "customers": max(int(30_000 * sf), 300),
            "suppliers": max(int(2_000 * sf), 250),
            "parts": 200_000 * int(1 + math.log2(sf)) if sf >= 1
            else max(int(200_000 * sf), 1_000)}


def generate(sf: float, seed: int) -> dict:
    n = sizes(sf)
    streams = iter(np.random.SeedSequence(seed).spawn(32))

    def rng():
        return np.random.default_rng(next(streams))

    # --- dimensions: one packed word a row, gathered once a line -----------
    r = rng()
    c_pack = (r.integers(0, 250, n["customers"], dtype=np.int32)
              | (r.integers(0, 5, n["customers"], dtype=np.int32) << 8))
    r = rng()
    s_city = r.integers(0, 250, n["suppliers"], dtype=np.int16)
    r = rng()
    p_pack = (r.integers(0, 1000, n["parts"], dtype=np.int64)
              | (r.integers(0, len(COLORS), n["parts"], dtype=np.int64) << 10)
              | (r.integers(0, len(TYPES), n["parts"], dtype=np.int64) << 17)
              | (r.integers(0, len(CONTAINERS), n["parts"], dtype=np.int64) << 25)
              | (r.integers(1, 51, n["parts"], dtype=np.int64) << 31))

    # --- orders, in the order of the table's key (date, then order key) ----
    r = rng()
    no = n["orders"]
    o_date = r.integers(START_DATE, END_DATE + 1, no, dtype=np.int32)
    o_cust = r.integers(1, n["customers"] + 1, no, dtype=np.int32)
    o_prio = r.integers(0, 5, no, dtype=np.int8)
    o_lines = r.integers(1, 8, no, dtype=np.int8)
    # dbgen's sparse keys, every BACKENDS-th order of the sequence; keys
    # ascend, so a stable sort by date leaves each day's in key order
    o_key = (np.arange(no, dtype=np.int64) * 32 + 1).astype(np.int32)
    by_date = np.argsort((o_date - START_DATE).astype(np.int16), kind="stable")
    o_date, o_cust, o_prio, o_key = (a[by_date] for a in
                                     (o_date, o_cust, o_prio, o_key))
    # o_lines is drawn iid: it needs no reordering
    nl = int(o_lines.sum(dtype=np.int64))
    first = np.zeros(no, dtype=np.int64)
    np.cumsum(o_lines[:-1], out=first[1:])
    order_of = np.repeat(np.arange(no, dtype=np.int32), o_lines)

    cols: dict = {}
    pool = ThreadPoolExecutor(6)
    jobs = []

    def later(fn, *args):
        jobs.append(pool.submit(fn, *args))

    def order_columns():
        cols["LO_ORDERDATE"] = o_date[order_of]
        cols["LO_ORDERKEY"] = o_key[order_of]
        line = np.arange(1, nl + 1, dtype=np.int32)
        line -= first.astype(np.int32)[order_of]
        cols["LO_LINENUMBER"] = line.astype(np.int8)
        cols["LO_ORDERPRIORITY"] = o_prio[order_of].astype(np.int32)

    def customer_columns(lut_city, lut_nation, lut_region, lut_segment):
        cust = o_cust[order_of]
        cols["LO_CUSTKEY"] = cust
        pack = c_pack[cust - 1]
        city = pack & 0xFF
        cols["C_CITY"] = lut_city[city]
        cols["C_NATION"] = lut_nation[city // 10]
        cols["C_REGION"] = lut_region[city // 10]
        cols["C_MKTSEGMENT"] = lut_segment[pack >> 8]

    def supplier_columns(r, lut_city, lut_nation, lut_region):
        supp = r.integers(1, n["suppliers"] + 1, nl, dtype=np.int32)
        cols["LO_SUPPKEY"] = supp
        city = s_city[supp - 1]
        cols["S_CITY"] = lut_city[city]
        cols["S_NATION"] = lut_nation[city // 10]
        cols["S_REGION"] = lut_region[city // 10]

    def part_columns(r, r2, lut_brand, lut_color, lut_type, lut_container):
        part = r.integers(1, n["parts"] + 1, nl, dtype=np.int32)
        cols["LO_PARTKEY"] = part
        pack = p_pack[part - 1]
        brand = (pack & 0x3FF).astype(np.int32)
        cols["P_BRAND"] = lut_brand[brand]
        # MFGR#11 .. MFGR#55 and MFGR#1 .. MFGR#5 sort as they count
        cols["P_CATEGORY"] = brand // 40
        cols["P_MFGR"] = brand // 200
        cols["P_COLOR"] = lut_color[(pack >> 10) & 0x7F]
        cols["P_TYPE"] = lut_type[(pack >> 17) & 0xFF]
        cols["P_CONTAINER"] = lut_container[(pack >> 25) & 0x3F]
        cols["P_SIZE"] = (pack >> 31).astype(np.int8)
        del pack, brand
        money(part, r2)

    def money(part, r):
        qty = r.integers(1, 51, nl, dtype=np.int8)
        disc = r.integers(0, 11, nl, dtype=np.int8)
        tax = r.integers(0, 9, nl, dtype=np.int8)
        cols["LO_QUANTITY"], cols["LO_DISCOUNT"], cols["LO_TAX"] = qty, disc, tax
        retail = 90000 + (part // 10) % 20001 + 100 * (part % 1000)  # cents
        ext = retail * qty
        cols["LO_EXTENDEDPRICE"] = ext
        cols["LO_SUPPLYCOST"] = 6 * retail // 10
        revenue = ext.astype(np.int64) * (100 - disc.astype(np.int16)) // 100
        cols["LO_REVENUE"] = revenue.astype(np.int32)
        gross = revenue * (100 + tax.astype(np.int16)) // 100
        total = np.add.reduceat(gross, first)
        cols["LO_ORDTOTALPRICE"] = total.astype(np.int32)[order_of]

    def line_columns(r):
        cols["LO_COMMITDATE"] = (
            cols["LO_ORDERDATE"]
            + r.integers(30, 91, nl, dtype=np.int8)).astype(np.int32)
        cols["LO_SHIPMODE"] = r.integers(0, len(SHIPMODES), nl, dtype=np.int32)

    city_dict, lut_city = _coded(CITIES)
    nation_dict, lut_nation = _coded([name for name, _ in NATIONS])
    region_dict, region_code = _coded(REGIONS)
    lut_region = region_code[[region for _, region in NATIONS]]
    segment_dict, lut_segment = _coded(SEGMENTS)
    brand_dict, lut_brand = _coded(BRANDS)
    color_dict, lut_color = _coded(COLORS)
    type_dict, lut_type = _coded(TYPES)
    container_dict, lut_container = _coded(CONTAINERS)

    order_job = pool.submit(order_columns)
    later(customer_columns, lut_city, lut_nation, lut_region, lut_segment)
    later(supplier_columns, rng(), lut_city, lut_nation, lut_region)
    later(part_columns, rng(), rng(), lut_brand, lut_color, lut_type,
          lut_container)
    order_job.result()
    later(line_columns, rng())
    for job in jobs:
        job.result()
    pool.shutdown()

    blank = np.zeros(nl, dtype=np.int32)  # one array for every stand-in text
    for name in ("C_NAME", "C_ADDRESS", "C_PHONE", "S_NAME", "S_ADDRESS",
                 "S_PHONE", "P_NAME"):
        cols[name] = blank
    cols["LO_SHIPPRIORITY"] = np.zeros(nl, dtype=np.int8)

    def text(name, values=None):
        d = values if isinstance(values, StringDict) else (
            StringDict.from_values(values if values is not None else [""]))
        return Field(name, T.VARCHAR, False, d)

    def number(name, t):
        return Field(name, t, False)

    schema = Schema((
        number("LO_ORDERDATE", T.DATE), number("LO_ORDERKEY", T.INT),
        number("LO_LINENUMBER", T.TINYINT), number("LO_CUSTKEY", T.INT),
        number("LO_PARTKEY", T.INT), number("LO_SUPPKEY", T.INT),
        text("LO_ORDERPRIORITY", PRIORITIES),
        number("LO_SHIPPRIORITY", T.TINYINT), number("LO_QUANTITY", T.TINYINT),
        number("LO_EXTENDEDPRICE", T.INT), number("LO_ORDTOTALPRICE", T.INT),
        number("LO_DISCOUNT", T.TINYINT), number("LO_REVENUE", T.INT),
        number("LO_SUPPLYCOST", T.INT), number("LO_TAX", T.TINYINT),
        number("LO_COMMITDATE", T.DATE), text("LO_SHIPMODE", SHIPMODES),
        text("C_NAME"), text("C_ADDRESS"), text("C_CITY", city_dict),
        text("C_NATION", nation_dict), text("C_REGION", region_dict),
        text("C_PHONE"), text("C_MKTSEGMENT", segment_dict),
        text("S_NAME"), text("S_ADDRESS"), text("S_CITY", city_dict),
        text("S_NATION", nation_dict), text("S_REGION", region_dict),
        text("S_PHONE"), text("P_NAME"), text("P_MFGR", MFGRS),
        text("P_CATEGORY", CATEGORIES), text("P_BRAND", brand_dict),
        text("P_COLOR", color_dict), text("P_TYPE", type_dict),
        number("P_SIZE", T.TINYINT), text("P_CONTAINER", container_dict),
    ))
    return {"lineorder_flat": HostTable(schema, cols)}
