"""Would the comparison that decides `correct` catch sums kept in float32?

A configuration whose guarantees say "exact int64 sums" is held to them by
`benchmarks/harness/compare.py`, which compares numbers to 1e-6 relative. The
control: every reference of a cell computed again with each `astype("int64")`
of its own code a `astype("float32")` (products, differences and sums then
run in float32, as a kernel that accumulates in f32 would), its rows put
through `compare.first_mismatch` against the exact reference's. A statement
that still reads as correct is one the yardstick cannot hold to exactness at
this size.

    python tools/lower_precision_control.py <cell> [scale]

Host only (pandas); at the configuration's own scale run it where the tables
fit. Prints one line a statement and a count."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import pandas as pd  # noqa: E402

from benchmarks.harness import cells, compare, sut  # noqa: E402


def main(name: str, scale: float | None) -> int:
    cell = cells.Cell(ROOT, name)
    scale = cell.config["scale_factor"] if scale is None else scale
    tables, _ = sut.make_tables(cell.config, ROOT, scale)
    frames = compare.frames(tables, compare.union_columns(
        [t["oracle"].COLUMNS for t in cell.templates]))
    print(f"control cell={name} scale={scale} rows="
          f"{ {t: tables[t].num_rows for t in frames} }")
    exact_astype = pd.Series.astype

    def narrow(self, dtype, *args, **kwargs):
        if dtype == "int64":
            dtype = "float32"
        return exact_astype(self, dtype, *args, **kwargs)

    passed = 0
    for v in cell.variants:
        oracle = v["oracle"]
        exact = oracle.expected(frames, **v["params"])
        pd.Series.astype = narrow
        try:
            low = oracle.expected(frames, **v["params"])
        finally:
            pd.Series.astype = exact_astype
        rows = [tuple(None if x is None else str(x) for x in r)
                for r in low.itertuples(index=False)]
        verdict = compare.first_mismatch(rows, exact, oracle.KEY)
        differ = sum(a != b for a, b in zip(
            map(tuple, low.itertuples(index=False)),
            map(tuple, exact.itertuples(index=False))))
        passed += verdict is None
        print(f"control {v['name']} rows={len(exact)} rows_not_equal={differ} "
              f"reads_as={'correct' if verdict is None else 'NOT correct: ' + verdict}")
    print(f"control float32 sums read as correct in {passed} of "
          f"{len(cell.variants)} statements")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], float(sys.argv[2]) if len(sys.argv) > 2 else None))
