"""The comparison that decides `correct`: rows as the MySQL text protocol
returned them against the rows of the statement's plain pandas reference, at
chip_smoke.py's tolerance (numbers within 1e-6 relative, dates by day, text
as text), and the frames the references read, built here from the generated
tables and not by the program."""

from __future__ import annotations

import math

import numpy as np
import pandas as pd


def frames(tables: dict, columns: dict) -> dict:
    """{table: DataFrame of the named columns}: DECIMAL as float64 of its
    logical value, DATE as datetime64, VARCHAR as a categorical over the
    column's dictionary (60M Python strings would take minutes)."""
    out = {}
    for table, names in columns.items():
        t = tables[table]
        cols = {}
        for field in t.schema.fields:
            if field.name not in names:
                continue
            a = t.arrays[field.name]
            if field.type.is_string:
                s = pd.Series(pd.Categorical.from_codes(
                    a, categories=list(field.dict.values)))
            elif field.type.is_decimal:
                s = pd.Series(a / 10 ** field.type.scale)
            elif field.type.kind.name == "DATE":
                # days since 1970 as datetime64[s], the coarsest unit pandas
                # keeps (a [D] array would be converted, one copy more)
                s = pd.Series((a.astype(np.int64) * 86400).view("datetime64[s]"))
            else:
                s = pd.Series(a)
            if field.name in t.valids:
                s = s.mask(~t.valids[field.name])
            cols[field.name] = s
        missing = set(names) - set(cols)
        if missing:
            raise KeyError(f"{table} has no columns {sorted(missing)}")
        out[table] = pd.DataFrame(cols, copy=False)
    return out


def union_columns(column_sets: list) -> dict:
    out: dict = {}
    for columns in column_sets:
        for table, names in columns.items():
            out[table] = tuple(dict.fromkeys(out.get(table, ()) + tuple(names)))
    return out


def _cell_ok(got, exp) -> bool:
    if exp is None or (isinstance(exp, float) and math.isnan(exp)):
        return got is None
    if got is None:
        return False
    if isinstance(exp, (pd.Timestamp, np.datetime64)):
        return str(got)[:10] == str(exp)[:10]
    if isinstance(exp, (int, float, np.integer, np.floating)):
        g, e = float(got), float(exp)
        return abs(g - e) <= max(abs(e), 1.0) * 1e-6
    return str(got) == str(exp)


def first_mismatch(got: list, expected, key) -> str | None:
    """None when the rows agree, else the first difference. `expected` is
    the reference's DataFrame; `key` is the result column that matches rows
    where the statement's ORDER BY may tie (None: by position)."""
    exp = [tuple(r) for r in expected.itertuples(index=False)]
    if len(got) != len(exp):
        return f"{len(got)} rows, reference has {len(exp)}"
    if key is not None:
        got = sorted(got, key=lambda r: int(r[key]))
        exp = sorted(exp, key=lambda r: int(r[key]))
    for i, (g, e) in enumerate(zip(got, exp)):
        if len(g) != len(e):
            return f"row {i}: {len(g)} cells, reference has {len(e)}"
        for j, (gv, ev) in enumerate(zip(g, e)):
            if not _cell_ok(gv, ev):
                return f"row {i} column {j}: {gv!r}, reference has {ev!r}"
    return None
