"""TPC-H Q3 (shipping priority) in pandas: the plain reference for
`statements/tpch/q3.sql`, copied from tests/tpch_oracle.py."""

import pandas as pd

COLUMNS = {"customer": ("c_custkey", "c_mktsegment"),
           "orders": ("o_orderkey", "o_custkey", "o_orderdate",
                      "o_shippriority"),
           "lineitem": ("l_orderkey", "l_extendedprice", "l_discount",
                        "l_shipdate")}
# the top 10 may tie on (revenue, o_orderdate): rows are matched by the
# unique l_orderkey (result column 0), not by position
KEY = 0


def expected(f):
    c, o, li = f["customer"], f["orders"], f["lineitem"]
    day = pd.Timestamp("1995-03-15")
    j = (c[c.c_mktsegment == "BUILDING"]
         .merge(o[o.o_orderdate < day], left_on="c_custkey", right_on="o_custkey")
         .merge(li[li.l_shipdate > day], left_on="o_orderkey", right_on="l_orderkey"))
    j = j.assign(rev=j.l_extendedprice * (1 - j.l_discount))
    g = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                  as_index=False).agg(revenue=("rev", "sum"))
    g = g[["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]]
    return g.sort_values(["revenue", "o_orderdate"], ascending=[False, True]).head(10)
