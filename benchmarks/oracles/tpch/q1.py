"""TPC-H Q1 (pricing summary report) in pandas: the plain reference for
`statements/tpch/q1.sql`, copied from tests/tpch_oracle.py."""

import pandas as pd

COLUMNS = {"lineitem": ("l_quantity", "l_extendedprice", "l_discount", "l_tax",
                        "l_returnflag", "l_linestatus", "l_shipdate")}
KEY = None  # ORDER BY l_returnflag, l_linestatus is total


def expected(f):
    li = f["lineitem"]
    x = li[li.l_shipdate <= pd.Timestamp("1998-09-02")].assign(
        disc_price=lambda r: r.l_extendedprice * (1 - r.l_discount),
        charge=lambda r: r.l_extendedprice * (1 - r.l_discount) * (1 + r.l_tax),
    )
    g = x.groupby(["l_returnflag", "l_linestatus"], as_index=False,
                  observed=True).agg(
        sum_qty=("l_quantity", "sum"), sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"), sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"), avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"), count_order=("l_quantity", "size"),
    )
    return g.sort_values(["l_returnflag", "l_linestatus"])
