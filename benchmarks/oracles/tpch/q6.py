"""TPC-H Q6 (forecasting revenue change) in pandas: the plain reference for
`statements/tpch/q6.sql`, copied from tests/tpch_oracle.py, with the
statement's substitution parameters (TPC-H 2.4.6.3: DATE = 1 January of
year, DISCOUNT +- 0.01 written as its two bounds, QUANTITY)."""

import pandas as pd

COLUMNS = {"lineitem": ("l_quantity", "l_extendedprice", "l_discount",
                        "l_shipdate")}
KEY = None  # one row


def expected(f, year, disc_lo, disc_hi, qty):
    li = f["lineitem"]
    x = li[(li.l_shipdate >= pd.Timestamp(f"{year}-01-01"))
           & (li.l_shipdate < pd.Timestamp(f"{int(year) + 1}-01-01"))
           & (li.l_discount >= float(disc_lo)) & (li.l_discount <= float(disc_hi))
           & (li.l_quantity < float(qty))]
    return pd.DataFrame({"revenue": [(x.l_extendedprice * x.l_discount).sum()]})
