"""Every statement variant's expected rows, from its plain pandas reference.

The tables are the same in every run (the configuration's `data_seed`), so
the reference's answer is kept in `benchmarks/.cache/reference/` inside the
checkout and computed once per checkout, not once per run (Q1, Q3 and Q6 at
SF10 take 26 s with their frames). The key is a digest of everything the
answer depends on — configuration, generator, statement text, reference
file, frame builder, parameters, scale, numpy and pandas versions — so a
change to any of them computes it anew."""

from __future__ import annotations

import hashlib
import json
import os

import numpy as np
import pandas as pd

from . import compare


def _file_digest(*paths: str) -> str:
    h = hashlib.sha256()
    for path in paths:
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


class Reference:
    def __init__(self, cell, tables: dict, scale: float):
        self.cell, self.tables = cell, tables
        self.dir = os.path.join(cell.root, "benchmarks", ".cache", "reference")
        self._frames = None
        bench = os.path.join(cell.root, "benchmarks")
        self._common = json.dumps([
            _file_digest(
                os.path.join(bench, "datagen", cell.config["generator"] + ".py"),
                os.path.join(bench, "harness", "compare.py")),
            cell.config["data_seed"], scale, np.__version__, pd.__version__])

    def _key(self, variant: dict) -> str:
        return hashlib.sha256(json.dumps([
            self._common, variant["sql"], variant["params"],
            _file_digest(variant["oracle"].__file__)]).encode()).hexdigest()

    def frames(self) -> dict:
        if self._frames is None:
            self._frames = compare.frames(self.tables, compare.union_columns(
                [t["oracle"].COLUMNS for t in self.cell.templates]))
        return self._frames

    def expected(self, variant: dict) -> pd.DataFrame:
        path = os.path.join(self.dir, self._key(variant) + ".pkl")
        if os.path.exists(path):
            return pd.read_pickle(path)  # written below, by this checkout
        rows = variant["oracle"].expected(self.frames(), **variant["params"])
        os.makedirs(self.dir, exist_ok=True)
        rows.to_pickle(path + ".tmp")
        os.replace(path + ".tmp", path)
        return rows
