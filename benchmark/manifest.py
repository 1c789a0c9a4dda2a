"""``BENCHMARK.json`` and the files it names.

A later PR adds a configuration, a traffic mix or a metric as a file of its
own and an entry in the manifest, and edits no file that is there.  So
nothing here knows a name: a configuration is the ``file`` of its manifest
entry, a traffic mix is ``<path>/traffic/<traffic>.json`` and a metric is
``<path>/metrics/<name>.py``, looked for under every directory of the
manifest's ``paths``.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


class Manifest:
    def __init__(self, path: str = MANIFEST, root: str = ROOT):
        self.root = root
        with open(path) as f:
            self.doc = json.load(f)

    # --------------------------------------------------------------- lookups
    def _one(self, section: str, name: str) -> dict:
        hits = [e for e in self.doc[section] if e["name"] == name]
        if len(hits) != 1:
            known = [e["name"] for e in self.doc[section]]
            raise KeyError(f"{section} has no single {name!r}; known: {known}")
        return hits[0]

    def workload(self, name: str) -> dict:
        return self._one("workloads", name)

    def _find(self, sub: str, filename: str) -> str:
        tried = []
        for p in self.doc["paths"]:
            path = os.path.join(self.root, p, sub, filename)
            if os.path.isfile(path):
                return path
            tried.append(path)
        raise FileNotFoundError(f"none of {tried} exists")

    def config(self, name: str) -> dict:
        """The configuration's own file, as it is run."""
        entry = self._one("configs", name)
        with open(os.path.join(self.root, entry["file"])) as f:
            doc = json.load(f)
        doc.setdefault("name", name)
        return doc

    def traffic(self, name: str) -> dict:
        with open(self._find("traffic", name + ".json")) as f:
            doc = json.load(f)
        doc.setdefault("name", name)
        return doc

    def metric_entries(self, kind: str, workload: str) -> List[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports: a
        metric that lists ``workloads`` exists only in those."""
        return [
            m for m in self.doc[kind]
            if "workloads" not in m or workload in m["workloads"]
        ]

    def metric_reader(self, name: str):
        """The metric's own module: ``NAME``, ``UNIT``, ``SOURCE``, for a
        per-layer metric ``LAYER`` and ``MOVES``, and ``read(run, trace)``
        which returns a number, or None where it finds nothing to read."""
        path = self._find("metrics", name + ".py")
        spec = importlib.util.spec_from_file_location(
            "benchmark_metric_" + name.replace("-", "_").replace(".", "_"),
            path,
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def read_metrics(self, kind: str, workload: str, run: dict,
                     trace: Optional[dict]) -> Dict[str, dict]:
        """``{name: {"value", "unit"}}`` for every metric of the cell whose
        reader found something.  A per-layer metric is reported only where
        the end-to-end metric it moves is."""
        e2e = {m["name"] for m in self.metric_entries("end_to_end", workload)}
        out: Dict[str, dict] = {}
        for m in self.metric_entries(kind, workload):
            if kind == "per_layer" and m["moves"] not in e2e:
                continue
            value = self.metric_reader(m["name"]).read(run, trace)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out
