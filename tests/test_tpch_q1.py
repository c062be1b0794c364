"""End-to-end TPC-H Q1 with a hand-built physical plan, validated against a
pandas oracle (the SQL-regression-suite analog of SURVEY §4 tier 3)."""

import numpy as np
import pandas as pd

import jax

from starrocks_tpu.column import HostTable

# the single source of truth for the hand-built Q1 plan lives in the driver
# entry module; the test validates that exact plan
from __graft_entry__ import _q1_plan as tpch_q1


def q1_pandas(df, cutoff):
    f = df[df["l_shipdate"] <= cutoff]
    g = f.assign(
        disc_price=f.l_extendedprice * (1 - f.l_discount),
        charge=f.l_extendedprice * (1 - f.l_discount) * (1 + f.l_tax),
    ).groupby(["l_returnflag", "l_linestatus"], as_index=False).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "size"),
    )
    return g.sort_values(["l_returnflag", "l_linestatus"]).reset_index(drop=True)


def test_q1_vs_pandas():
    from starrocks_tpu.storage.datagen.tpch import gen_tpch

    li = gen_tpch(sf=0.01)["lineitem"]
    chunk = li.to_chunk()

    jq1 = jax.jit(tpch_q1)
    out, ng = jq1(chunk)
    got = pd.DataFrame(
        HostTable.from_chunk(out).to_pylist(),
        columns=["l_returnflag", "l_linestatus", "sum_qty", "sum_base_price",
                 "sum_disc_price", "sum_charge", "avg_qty", "avg_price",
                 "avg_disc", "count_order"],
    )

    df = li.to_pandas()
    exp = q1_pandas(df, pd.Timestamp("1998-09-02"))

    assert int(ng) == len(exp) == 4  # A/F, N/F, N/O, R/F
    assert list(got["l_returnflag"]) == list(exp["l_returnflag"])
    assert list(got["l_linestatus"]) == list(exp["l_linestatus"])
    np.testing.assert_allclose(got["sum_qty"], exp["sum_qty"], rtol=1e-12)
    np.testing.assert_allclose(got["sum_base_price"], exp["sum_base_price"], rtol=1e-12)
    # decimal (scale 4/6) vs float64 oracle: float64 is the imprecise one here
    np.testing.assert_allclose(got["sum_disc_price"], exp["sum_disc_price"], rtol=1e-9)
    np.testing.assert_allclose(got["sum_charge"], exp["sum_charge"], rtol=1e-9)
    np.testing.assert_allclose(got["avg_qty"], exp["avg_qty"], rtol=1e-9)
    np.testing.assert_allclose(got["avg_price"], exp["avg_price"], rtol=1e-9)
    np.testing.assert_allclose(got["avg_disc"], exp["avg_disc"], rtol=1e-9)
    np.testing.assert_array_equal(got["count_order"], exp["count_order"])
