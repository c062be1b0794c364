"""From a cell's name to everything that defines it, by name and by file.

`BENCHMARK.json` lists cells, configurations and metrics; whatever belongs to
one of them sits in a file of its own under `benchmarks/`, found here by the
name in the entry. A later PR adds entries and files and edits none:

    workloads[].config   -> configs[].file         (benchmarks/configs/<config>.json)
    workloads[].traffic  -> traffic/<traffic>.json
    traffic statements   -> statements/<source>/<id>.sql + oracles/<source>/<id>.py
    config "generator"   -> datagen/<generator>.py
    end_to_end[].name    -> e2e_metrics/<name>.py
    per_layer[].name     -> layer_metrics/<name>.py
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

_NAME = re.compile(r"[A-Za-z0-9][A-Za-z0-9_.\-]*\Z")


def load_module(root: str, *parts: str):
    """The module at benchmarks/<parts...>.py, imported by path."""
    for p in parts:
        if not all(_NAME.match(seg) for seg in p.split("/")):
            raise ValueError(f"not a plain name: {p!r}")
    path = os.path.join(root, "benchmarks", *parts) + ".py"
    spec = importlib.util.spec_from_file_location(
        "benchmarks_" + "_".join(parts).replace("/", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str):
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of `workloads`, resolved."""

    def __init__(self, root: str, name: str):
        bench = _read_json(os.path.join(root, "BENCHMARK.json"))
        entries = [w for w in bench["workloads"] if w["name"] == name]
        if len(entries) != 1:
            raise KeyError(f"BENCHMARK.json has no one workload {name!r}: "
                           f"{[w['name'] for w in bench['workloads']]}")
        self.root, self.name = root, name
        self.chips = entries[0]["chips"]
        config_entry = next(c for c in bench["configs"]
                            if c["name"] == entries[0]["config"])
        self.config = _read_json(os.path.join(root, config_entry["file"]))
        if self.config["chips"] != self.chips:
            raise ValueError(f"{name}: cell asks for {self.chips} chips, its "
                             f"configuration for {self.config['chips']}")
        self.traffic = _read_json(os.path.join(
            root, "benchmarks", "traffic", entries[0]["traffic"] + ".json"))

        def mine(metrics):
            return [m for m in metrics
                    if name in m.get("workloads", [name])]

        self.end_to_end = mine(bench["end_to_end"])
        self.per_layer = mine(bench["per_layer"])
        e2e_here = {m["name"] for m in self.end_to_end}
        astray = [m["name"] for m in self.per_layer
                  if m["moves"] not in e2e_here]
        if astray:
            raise ValueError(
                f"{name}: per-layer metrics {astray} move end-to-end metrics "
                f"this cell does not report; list their cells under "
                f'"workloads" in BENCHMARK.json')

        # templates and their variants (one per parameter set), in file order
        self.templates, self.variants = [], []
        for st in self.traffic["statements"]:
            source, sid = st["source"], st["id"]
            with open(os.path.join(root, "benchmarks", "statements", source,
                                   sid + ".sql")) as f:
                text = f.read().strip()
            oracle = load_module(root, "oracles", source, sid)
            template = {"name": f"{source}.{sid}", "oracle": oracle}
            self.templates.append(template)
            for i, params in enumerate(st.get("params") or [{}]):
                self.variants.append({
                    "name": f"{source}.{sid}#{i}", "template": template["name"],
                    "params": params, "oracle": oracle,
                    "sql": text.format(**params) if params else text})

    def readers(self, traced: bool) -> list:
        """[(BENCHMARK.json entry, its reader module)] of the metrics this
        run prints: the cell's end-to-end ones, or with a trace its per-layer
        ones."""
        kind, entries = (("layer_metrics", self.per_layer) if traced
                         else ("e2e_metrics", self.end_to_end))
        return [(m, load_module(self.root, kind, m["name"])) for m in entries]
