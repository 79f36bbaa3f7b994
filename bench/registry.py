"""Finds a cell, its configuration, its traffic mix and its metrics by
name, from files: ``BENCHMARK.json`` names them, and each lives in a file
of its own under ``bench/``.  Adding a cell, a configuration, a mix or a
metric adds files and entries and edits none."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class Registry:
    def __init__(self, benchmark: Path = ROOT / "BENCHMARK.json", *,
                 configs: Path = BENCH_DIR / "configs",
                 traffic: Path = BENCH_DIR / "traffic",
                 metrics: Path = BENCH_DIR / "metrics",
                 references: Path = BENCH_DIR / "reference") -> None:
        if not benchmark.is_file():
            raise FileNotFoundError(f"no benchmark file {benchmark}")
        self.spec = json.loads(benchmark.read_text())
        self.configs = configs
        self.traffic = traffic
        self.metrics = metrics
        self.references = references
        self._readers: Dict[str, object] = {}

    def cell(self, name: str) -> Dict:
        for c in self.spec["workloads"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: "
                       f"{[c['name'] for c in self.spec['workloads']]}")

    def config(self, name: str) -> Dict:
        path = self.configs / f"{name}.json"
        if not path.is_file():
            raise FileNotFoundError(f"configuration {name!r}: no file {path}")
        return json.loads(path.read_text())

    def mix(self, name: str) -> Dict:
        path = self.traffic / f"{name}.json"
        if not path.is_file():
            raise FileNotFoundError(f"traffic mix {name!r}: no file {path}")
        return json.loads(path.read_text())

    def metric_entries(self, cell: str, kind: str) -> List[Dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports:
        those with no ``workloads`` key, and those that list the cell."""
        return [m for m in self.spec[kind]
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str):
        """The module ``bench/metrics/<metric>.py``; its ``read(window)``
        returns the value, or None where it finds nothing to read."""
        if metric not in self._readers:
            self._readers[metric] = _load(self.metrics / f"{metric}.py",
                                          f"bench_metric_{metric}")
        return self._readers[metric]

    def reference(self, name: str):
        return _load(self.references / f"{name}.py", f"bench_ref_{name}")


def _load(path: Path, module_name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        module_name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metric(registry: Registry, entry: Dict, window) -> Optional[Dict]:
    value = registry.reader(entry["name"]).read(window)
    if value is None:
        return None
    return {"value": float(value), "unit": entry["unit"]}
