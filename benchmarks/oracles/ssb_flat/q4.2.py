"""SSB Q4.2 on `lineorder_flat` in pandas: the plain reference for
`statements/ssb_flat/q4.2.sql`, written from the statement's meaning (profit
by year, supplier nation and category, 1997 and 1998).
Integer columns are widened to int64 before any arithmetic, so every sum is
exact."""

import pandas as pd

COLUMNS = {"lineorder_flat": ("LO_ORDERDATE", "LO_REVENUE", "LO_SUPPLYCOST",
                              "C_REGION", "S_NATION", "S_REGION", "P_MFGR",
                              "P_CATEGORY")}
KEY = None  # ORDER BY names every group column: total


def expected(f):
    t = f["lineorder_flat"]
    x = t[(t.C_REGION == "AMERICA") & (t.S_REGION == "AMERICA")
          & (t.LO_ORDERDATE >= pd.Timestamp("1997-01-01"))
          & (t.LO_ORDERDATE <= pd.Timestamp("1998-12-31"))
          & t.P_MFGR.isin(["MFGR#1", "MFGR#2"])]
    x = x.assign(year=x.LO_ORDERDATE.dt.year,
                 profit64=(x.LO_REVENUE.astype("int64")
                           - x.LO_SUPPLYCOST.astype("int64")))
    g = x.groupby(["year", "S_NATION", "P_CATEGORY"], as_index=False,
                  observed=True).agg(profit=("profit64", "sum"))
    g = g.sort_values(["year", "S_NATION", "P_CATEGORY"])
    return g[["year", "S_NATION", "P_CATEGORY", "profit"]].astype(
        {"S_NATION": str, "P_CATEGORY": str})
