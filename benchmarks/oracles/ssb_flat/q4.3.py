"""SSB Q4.3 on `lineorder_flat` in pandas: the plain reference for
`statements/ssb_flat/q4.3.sql`, written from the statement's meaning (profit
by year, supplier city and brand for one category and suppliers of the United
States, 1997 and 1998).
Integer columns are widened to int64 before any arithmetic, so every sum is
exact."""

import pandas as pd

COLUMNS = {"lineorder_flat": ("LO_ORDERDATE", "LO_REVENUE", "LO_SUPPLYCOST",
                              "S_CITY", "S_NATION", "P_CATEGORY", "P_BRAND")}
KEY = None  # ORDER BY names every group column: total


def expected(f):
    t = f["lineorder_flat"]
    x = t[(t.S_NATION == "UNITED STATES")
          & (t.LO_ORDERDATE >= pd.Timestamp("1997-01-01"))
          & (t.LO_ORDERDATE <= pd.Timestamp("1998-12-31"))
          & (t.P_CATEGORY == "MFGR#14")]
    x = x.assign(year=x.LO_ORDERDATE.dt.year,
                 profit64=(x.LO_REVENUE.astype("int64")
                           - x.LO_SUPPLYCOST.astype("int64")))
    g = x.groupby(["year", "S_CITY", "P_BRAND"], as_index=False,
                  observed=True).agg(profit=("profit64", "sum"))
    g = g.sort_values(["year", "S_CITY", "P_BRAND"])
    return g[["year", "S_CITY", "P_BRAND", "profit"]].astype(
        {"S_CITY": str, "P_BRAND": str})
