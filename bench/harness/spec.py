"""``BENCHMARK.json`` and the files it names, each found by its name.

* a configuration: the ``file`` of its ``configs`` entry;
* a traffic mix: ``traffic/<traffic>.json``;
* a cell's settings and the limits of its correctness check:
  ``cells/<workload>.json``;
* a per-layer metric: the reader ``metrics/<name>.py``, or for a metric
  split by suffix (``decode_step_ms.online``) the reader of its base name,
  ``metrics/decode_step_ms.py``. A reader defines ``read(run)`` and returns
  a number, or None where it finds nothing to read.

Adding any of these adds files and a ``BENCHMARK.json`` entry; no existing
file changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

BENCH = Path(__file__).resolve().parents[1]


class Spec:
    def __init__(self, root: Path):
        """``root``: the directory holding ``BENCHMARK.json``."""
        self.root = Path(root)
        self.bench = json.loads((self.root / "BENCHMARK.json").read_text())
        self.dir = self.root / self.bench["paths"][0]

    def workload(self, name: str) -> Dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> Dict:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def cell(self, workload: str) -> Dict:
        return json.loads((self.dir / "cells" / f"{workload}.json")
                          .read_text())

    def peaks(self, device_kind: str) -> Dict:
        table = json.loads((self.dir / "peaks.json").read_text())
        if device_kind not in table:
            raise KeyError(f"device {device_kind!r} is not in the peaks "
                           "table")
        return table[device_kind]

    def end_to_end(self, workload: str) -> List[Dict]:
        return [m for m in self.bench["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> List[Dict]:
        return [m for m in self.bench["per_layer"]
                if workload in m.get("workloads", [workload])]

    def reader(self, metric: str) -> Callable:
        d = self.dir / "metrics"
        path = d / f"{metric}.py"
        if not path.exists():
            path = d / f"{metric.split('.')[0]}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{path.stem}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
