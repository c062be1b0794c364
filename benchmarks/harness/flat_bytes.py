"""The bytes a statement over a flat table has to read, from the table's
declaration and not from what holds it: every column its oracle file says it
reads (`COLUMNS`), once a row, at the narrowest width its declared type and
its dictionary admit:

    TINYINT, BOOLEAN 1    SMALLINT 2    INT, DATE, FLOAT 4
    BIGINT, DECIMAL, DATETIME, DOUBLE 8
    VARCHAR: a dictionary of <= 256 values 1, of <= 65,536 values 2, else 4
    plus one byte a row where the column has a validity mask

`bytes.py`'s `scan_bytes` counts what the host table holds (a dictionary
code is four bytes there whatever the dictionary), so a program that narrows
what it keeps resident would read past 100% of that roofline. This count is
the same work whatever implements it: a share of it cannot pass 100% unless
the program reads less than the statement names."""

from __future__ import annotations

_WIDTH = {"BOOLEAN": 1, "TINYINT": 1, "SMALLINT": 2, "INT": 4, "DATE": 4,
          "FLOAT": 4, "BIGINT": 8, "DECIMAL": 8, "DATETIME": 8, "DOUBLE": 8}


def column_width(field) -> int:
    """Bytes a value of this column of a table's schema needs."""
    kind = field.type.kind.name
    if kind == "VARCHAR":
        values = len(field.dict) if field.dict is not None else 1 << 32
        return 1 if values <= 256 else 2 if values <= 65536 else 4
    if kind not in _WIDTH:
        raise KeyError(f"{field.name}: no scan width for a {kind} column")
    return _WIDTH[kind]


def flat_scan_bytes(tables: dict, columns: dict) -> int:
    total = 0
    for table, names in columns.items():
        t = tables[table]
        fields = {f.name: f for f in t.schema.fields}
        for name in names:
            total += t.num_rows * (column_width(fields[name])
                                   + (1 if name in t.valids else 0))
    return total
