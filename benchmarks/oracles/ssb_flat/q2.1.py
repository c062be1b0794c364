"""SSB Q2.1 on `lineorder_flat` in pandas: the plain reference for
`statements/ssb_flat/q2.1.sql`, written from the statement's meaning (revenue
by year and brand for one category and suppliers of one region).
Integer columns are widened to int64 before any arithmetic, so every sum is
exact."""

import pandas as pd

COLUMNS = {"lineorder_flat": ("LO_ORDERDATE", "LO_REVENUE", "P_CATEGORY",
                              "P_BRAND", "S_REGION")}
KEY = None  # ORDER BY names every group column: total


def expected(f):
    t = f["lineorder_flat"]
    x = t[(t.P_CATEGORY == "MFGR#12") & (t.S_REGION == "AMERICA")]
    x = x.assign(year=x.LO_ORDERDATE.dt.year,
                 revenue64=x.LO_REVENUE.astype("int64"))
    g = x.groupby(["year", "P_BRAND"], as_index=False,
                  observed=True).agg(revenue=("revenue64", "sum"))
    g = g.sort_values(["year", "P_BRAND"])
    return g[["revenue", "year", "P_BRAND"]].astype(
        {"P_BRAND": str})
