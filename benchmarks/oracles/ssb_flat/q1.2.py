"""SSB Q1.2 on `lineorder_flat` in pandas: the plain reference for
`statements/ssb_flat/q1.2.sql`, written from the statement's meaning (January
1994, discounts 4 to 6, quantities 26 to 35).
Integer columns are widened to int64 before any arithmetic, so every sum is
exact."""

import pandas as pd

COLUMNS = {"lineorder_flat": ("LO_ORDERDATE", "LO_QUANTITY",
                              "LO_EXTENDEDPRICE", "LO_DISCOUNT")}
KEY = None  # one row


def expected(f):
    t = f["lineorder_flat"]
    x = t[(t.LO_ORDERDATE >= pd.Timestamp("1994-01-01"))
          & (t.LO_ORDERDATE <= pd.Timestamp("1994-01-31"))
          & (t.LO_DISCOUNT >= 4) & (t.LO_DISCOUNT <= 6)
          & (t.LO_QUANTITY >= 26) & (t.LO_QUANTITY <= 35)]
    revenue = (x.LO_EXTENDEDPRICE.astype("int64")
               * x.LO_DISCOUNT.astype("int64")).sum()
    return pd.DataFrame({"revenue": [int(revenue)]})
