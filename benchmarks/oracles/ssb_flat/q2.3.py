"""SSB Q2.3 on `lineorder_flat` in pandas: the plain reference for
`statements/ssb_flat/q2.3.sql`, written from the statement's meaning (revenue
by year for one brand and European suppliers).
Integer columns are widened to int64 before any arithmetic, so every sum is
exact."""

import pandas as pd

COLUMNS = {"lineorder_flat": ("LO_ORDERDATE", "LO_REVENUE", "P_BRAND",
                              "S_REGION")}
KEY = None  # ORDER BY names every group column: total


def expected(f):
    t = f["lineorder_flat"]
    x = t[(t.P_BRAND == "MFGR#2239") & (t.S_REGION == "EUROPE")]
    x = x.assign(year=x.LO_ORDERDATE.dt.year,
                 revenue64=x.LO_REVENUE.astype("int64"))
    g = x.groupby(["year", "P_BRAND"], as_index=False,
                  observed=True).agg(revenue=("revenue64", "sum"))
    g = g.sort_values(["year", "P_BRAND"])
    return g[["revenue", "year", "P_BRAND"]].astype(
        {"P_BRAND": str})
