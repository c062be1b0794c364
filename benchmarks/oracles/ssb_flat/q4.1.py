"""SSB Q4.1 on `lineorder_flat` in pandas: the plain reference for
`statements/ssb_flat/q4.1.sql`, written from the statement's meaning (profit
by year and customer nation within America for two manufacturers).
Integer columns are widened to int64 before any arithmetic, so every sum is
exact."""

import pandas as pd

COLUMNS = {"lineorder_flat": ("LO_ORDERDATE", "LO_REVENUE", "LO_SUPPLYCOST",
                              "C_NATION", "C_REGION", "S_REGION", "P_MFGR")}
KEY = None  # ORDER BY names every group column: total


def expected(f):
    t = f["lineorder_flat"]
    x = t[(t.C_REGION == "AMERICA") & (t.S_REGION == "AMERICA")
          & t.P_MFGR.isin(["MFGR#1", "MFGR#2"])]
    x = x.assign(year=x.LO_ORDERDATE.dt.year,
                 profit64=(x.LO_REVENUE.astype("int64")
                           - x.LO_SUPPLYCOST.astype("int64")))
    g = x.groupby(["year", "C_NATION"], as_index=False,
                  observed=True).agg(profit=("profit64", "sum"))
    g = g.sort_values(["year", "C_NATION"])
    return g[["year", "C_NATION", "profit"]].astype(
        {"C_NATION": str})
