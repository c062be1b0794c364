"""SSB Q1.1 on `lineorder_flat` in pandas: the plain reference for
`statements/ssb_flat/q1.1.sql`, written from the statement's meaning (revenue
from 1993's orders at discounts 1 to 3 and quantities under 25).
Integer columns are widened to int64 before any arithmetic, so every sum is
exact."""

import pandas as pd

COLUMNS = {"lineorder_flat": ("LO_ORDERDATE", "LO_QUANTITY",
                              "LO_EXTENDEDPRICE", "LO_DISCOUNT")}
KEY = None  # one row


def expected(f):
    t = f["lineorder_flat"]
    x = t[(t.LO_ORDERDATE >= pd.Timestamp("1993-01-01"))
          & (t.LO_ORDERDATE <= pd.Timestamp("1993-12-31"))
          & (t.LO_DISCOUNT >= 1) & (t.LO_DISCOUNT <= 3) & (t.LO_QUANTITY < 25)]
    revenue = (x.LO_EXTENDEDPRICE.astype("int64")
               * x.LO_DISCOUNT.astype("int64")).sum()
    return pd.DataFrame({"revenue": [int(revenue)]})
