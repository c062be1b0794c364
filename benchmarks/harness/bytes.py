"""The bytes a statement has to move, from shapes: every column its oracle
file says it reads (`COLUMNS`), once, at the width the table holds it in
(DECIMAL and BIGINT 8 bytes, DATE, INT and dictionary codes 4, plus one byte a
row where a column has a validity mask). That is the least a scan can read
from HBM, so for a statement that also sorts, joins or spills intermediates
it is a floor and its roofline share an upper estimate of how far off the
memory bound the program is."""

from __future__ import annotations


def scan_bytes(tables: dict, columns: dict) -> int:
    total = 0
    for table, names in columns.items():
        t = tables[table]
        for name in names:
            total += t.arrays[name].nbytes
            if name in t.valids:
                total += t.valids[name].nbytes
    return total
