"""Finds a cell's data by name: the ``workloads`` entry in BENCHMARK.json,
its configuration file, its traffic mix, and the optional per-cell file.

A later PR adds a cell as one ``workloads`` entry plus new files under
``configs/``, ``traffic/`` and ``cells/``; nothing here names a cell, a
configuration, a mix or a metric.
"""
from __future__ import annotations

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def resolve(dotted: str):
    """``package.module:attr`` -> the attribute."""
    module, _, attr = dotted.partition(":")
    return getattr(importlib.import_module(module), attr)


class Cell:
    """One workload of BENCHMARK.json with everything it names loaded."""

    def __init__(self, name: str, root: str = ROOT):
        bench = load_benchmark(root)
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise KeyError(f"BENCHMARK.json has no workload {name!r}; it "
                           f"has {[w['name'] for w in bench['workloads']]}")
        cfg_entry = next(c for c in bench["configs"]
                         if c["name"] == entry["config"])
        self.name = name
        self.chips = int(entry["chips"])
        self.config_name = entry["config"]
        self.traffic_name = entry["traffic"]
        self.config = _load(os.path.join(root, cfg_entry["file"]))
        self.traffic = _load(os.path.join(
            root, "benchmarks", "traffic", f"{entry['traffic']}.json"))
        # what belongs to this pairing alone (the rate its knee sweep found)
        cell_file = os.path.join(root, "benchmarks", "cells", f"{name}.json")
        self.cell = _load(cell_file) if os.path.exists(cell_file) else {}
        self.traffic.update(self.cell.get("traffic", {}))
        self.kind = self.config["kind"]
        self.end_to_end = self._metrics(bench["end_to_end"])
        self.per_layer = self._metrics(bench["per_layer"])

    def _metrics(self, entries: list) -> list:
        return [m for m in entries
                if self.name in m.get("workloads", [self.name])]

    def sizes(self, rehearse: bool) -> dict:
        """The configuration's sizes; a rehearsal swaps in the toy sizes
        the configuration file carries beside the real ones."""
        cfg = json.loads(json.dumps(self.config))
        traffic = dict(self.traffic)
        if rehearse:
            for section, over in cfg.get("rehearsal", {}).items():
                # the model's sizes are the file's top-level keys, as in
                # the source's config.json; every other section is nested
                target = cfg if section == "model" else cfg[section]
                target.update(over)
            traffic.update(traffic.get("rehearsal", {}))
        return {"config": cfg, "traffic": traffic}
