"""SSB Q3.1 on `lineorder_flat` in pandas: the plain reference for
`statements/ssb_flat/q3.1.sql`, written from the statement's meaning (revenue
by customer nation, supplier nation and year within Asia, 1992 to 1997).
Integer columns are widened to int64 before any arithmetic, so every sum is
exact."""

import pandas as pd

COLUMNS = {"lineorder_flat": ("LO_ORDERDATE", "LO_REVENUE", "C_NATION",
                              "C_REGION", "S_NATION", "S_REGION")}
# ORDER BY year, revenue DESC: revenues of different groups do not tie
KEY = None


def expected(f):
    t = f["lineorder_flat"]
    x = t[(t.C_REGION == "ASIA") & (t.S_REGION == "ASIA")
          & (t.LO_ORDERDATE >= pd.Timestamp("1992-01-01"))
          & (t.LO_ORDERDATE <= pd.Timestamp("1997-12-31"))]
    x = x.assign(year=x.LO_ORDERDATE.dt.year,
                 revenue64=x.LO_REVENUE.astype("int64"))
    g = x.groupby(["C_NATION", "S_NATION", "year"], as_index=False,
                  observed=True).agg(revenue=("revenue64", "sum"))
    g = g.sort_values(["year", "revenue"],
                      ascending=[True, False])
    return g[["C_NATION", "S_NATION", "year", "revenue"]].astype(
        {"C_NATION": str, "S_NATION": str})
