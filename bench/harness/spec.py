"""Find a cell's files by the names ``BENCHMARK.json`` gives.

- configuration: the file its ``configs`` entry names;
- traffic mix: ``bench/traffic/<traffic>.json``;
- per-cell check limits: ``bench/checks/<workload>.json``;
- metric: ``bench/metrics/<metric>.py``, a module with ``read(run)``
  that returns a number, or None where it finds nothing to read.

Every path is taken relative to the checkout's root, so a copy of the
tree with files dropped in works the same way.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict


class Cell:
    def __init__(self, root: Path, name: str):
        self.root = Path(root)
        self.bench = json.loads((self.root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; BENCHMARK.json has "
                           f"{sorted(cells)}")
        self.workload = cells[name]
        self.name = name
        cfgs = {c["name"]: c for c in self.bench["configs"]}
        entry = cfgs[self.workload["config"]]
        self.config = json.loads((self.root / entry["file"]).read_text())
        self.traffic = json.loads((self.root / "bench" / "traffic" /
                                   f"{self.workload['traffic']}.json").read_text())
        self.checks = json.loads((self.root / "bench" / "checks" /
                                  f"{name}.json").read_text())
        self.peaks_table = json.loads((self.root / "bench" /
                                       "peaks.json").read_text())

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def metrics(self, trace: bool) -> Dict[str, dict]:
        """The metrics this cell reports: its end-to-end ones, or with a
        trace its per-layer ones (a metric with a ``workloads`` list is
        reported only in the cells it lists)."""
        group = self.bench["per_layer" if trace else "end_to_end"]
        return {m["name"]: m for m in group
                if self.name in m.get("workloads", [self.name])}

    def reader(self, metric: str) -> Callable:
        path = self.root / "bench" / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
