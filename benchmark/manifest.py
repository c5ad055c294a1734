"""``BENCHMARK.json`` and the files it names.

Whatever belongs to one configuration, one traffic mix, one per-layer
metric, one kernel or one kind of run sits in a file of its own, found
by the name the manifest gives — there is no registry to edit:

  configs/<config>.json   traffic/<traffic>.json   runners/<runner>.py
  metrics/<metric>.py     kernels/<pallas_name>.py

A name is looked for under every directory of the manifest's ``paths``
(relative to the manifest), then beside this file.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DEFAULT_MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


class Manifest:
    def __init__(self, path: str = DEFAULT_MANIFEST):
        self.path = os.path.abspath(path)
        self.root = os.path.dirname(self.path)
        with open(self.path) as f:
            self.data: Dict[str, Any] = json.load(f)
        self.dirs = [os.path.join(self.root, p) for p in self.data["paths"]]
        if HERE not in self.dirs:
            self.dirs.append(HERE)

    # -- lookups -----------------------------------------------------------
    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in {self.path} (has: {[w['name'] for w in self.data['workloads']]})")

    def find(self, kind: str, name: str, ext: str) -> str:
        for d in self.dirs:
            path = os.path.join(d, kind, name + ext)
            if os.path.isfile(path):
                return path
        raise FileNotFoundError(f"no {kind}/{name}{ext} under {self.dirs}")

    def config(self, name: str) -> Dict[str, Any]:
        for c in self.data["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no config {name!r} in {self.path}")

    def traffic(self, name: str) -> Dict[str, Any]:
        with open(self.find("traffic", name, ".json")) as f:
            return json.load(f)

    def module(self, kind: str, name: str):
        path = self.find(kind, name, ".py")
        spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name.replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    # -- which metrics a cell reports ---------------------------------------
    def end_to_end(self, cell: str) -> List[Dict[str, Any]]:
        return [m for m in self.data["end_to_end"] if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> List[Dict[str, Any]]:
        """A per-layer metric with no ``workloads`` key is owed by every
        cell that reports the end-to-end metric it moves."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.data["per_layer"]
                if (cell in m["workloads"] if "workloads" in m else m["moves"] in e2e)]

    def metric_entry(self, name: str) -> Optional[Dict[str, Any]]:
        for m in self.data["end_to_end"] + self.data["per_layer"]:
            if m["name"] == name:
                return m
        return None
