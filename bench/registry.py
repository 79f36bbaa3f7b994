"""Finds a cell, its configuration, its traffic mix, its metrics, its
model family and its reference by name, from files: ``BENCHMARK.json``
and the configuration file name them, and each lives in a file of its own
under ``bench/``.  Adding a cell, a configuration (of a new architecture
too), a mix or a metric adds files and entries and edits none."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
DEFAULT_FAMILY = "internlm2"


class Registry:
    def __init__(self, benchmark: Path = ROOT / "BENCHMARK.json", *,
                 configs: Path = BENCH_DIR / "configs",
                 traffic: Path = BENCH_DIR / "traffic",
                 metrics: Path = BENCH_DIR / "metrics",
                 references: Path = BENCH_DIR / "reference",
                 families: Path = BENCH_DIR / "families") -> None:
        if not benchmark.is_file():
            raise FileNotFoundError(f"no benchmark file {benchmark}")
        self.spec = json.loads(benchmark.read_text())
        self.configs = configs
        self.traffic = traffic
        self.metrics = metrics
        self.references = references
        self.families = families
        self._modules: Dict[str, object] = {}

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
        return self._module(self.metrics, metric, "bench_metric_")

    def reference(self, name: str):
        """The module ``bench/reference/<name>.py``: the plain reference
        whose ``logit_gaps(...)`` judges served tokens."""
        return self._module(self.references, name, "bench_ref_")

    def family(self, name: str):
        """The module ``bench/families/<name>.py``: the architecture's
        ``model_config``, weights and operation counts."""
        return self._module(self.families, name, "bench_family_")

    def family_of(self, cfgfile: Dict):
        """The family a configuration names by its ``"family"`` key; a
        file without the key is InternLM2."""
        return self.family(cfgfile.get("family", DEFAULT_FAMILY))

    def _module(self, directory: Path, name: str, prefix: str):
        if prefix + name not in self._modules:
            self._modules[prefix + name] = _load(directory / f"{name}.py",
                                                 prefix + name)
        return self._modules[prefix + name]


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
