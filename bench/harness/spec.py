"""Finds the pieces of a cell by name.

``BENCHMARK.json`` at the checkout's root names each cell's configuration and
traffic mix and lists the metrics. Every piece lives in a file of its own,
found by its name, so a new configuration, mix or metric is a new file plus
an entry, never an edit:

- configuration ``<c>``: the ``file`` its entry names (``bench/configs/``);
- traffic mix ``<t>``: ``bench/traffic/<t>.json``;
- metric ``<m>``: ``bench/metrics/<m>.py``, a reader with ``read(run)``
  returning a number, or None where the run holds nothing to read.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

#: The checkout's root: ``bench/harness`` is two levels below it.
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    end_to_end: bool
    workloads: Optional[List[str]]
    moves: Optional[str] = None
    bound: Optional[float] = None


@dataclass
class Cell:
    """One entry of ``workloads`` with its configuration and traffic."""

    name: str
    chips: int
    config_name: str
    config: Dict
    traffic_name: str
    traffic: Dict
    metrics: List[Metric]  # every metric the benchmark reports in this cell


class Spec:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))

    # ------------------------------------------------------------ metrics
    def all_metrics(self) -> List[Metric]:
        out = []
        for m in self.bench["end_to_end"]:
            out.append(Metric(m["name"], m["unit"], m["better"], m["source"],
                              True, m.get("workloads"), bound=m["bound"]))
        for m in self.bench["per_layer"]:
            out.append(Metric(m["name"], m["unit"], m["better"], m["source"],
                              False, m.get("workloads"), moves=m["moves"]))
        return out

    def metrics_of(self, cell: str) -> List[Metric]:
        e2e = [m for m in self.all_metrics() if m.end_to_end
               and (m.workloads is None or cell in m.workloads)]
        reported = {m.name for m in e2e}
        layer = [m for m in self.all_metrics() if not m.end_to_end
                 and m.moves in reported
                 and (m.workloads is None or cell in m.workloads)]
        return e2e + layer

    def reader(self, metric: str) -> Callable:
        path = os.path.join(self.root, "bench", "metrics", f"{metric}.py")
        mod_name = "bench_metric_" + "".join(
            ch if ch.isalnum() else "_" for ch in metric)
        spec = importlib.util.spec_from_file_location(mod_name, path)
        if spec is None or spec.loader is None:
            raise FileNotFoundError(f"no reader for metric {metric!r}: {path}")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    # --------------------------------------------------------------- cells
    def config(self, name: str) -> Dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                return load_json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> Dict:
        return load_json(os.path.join(self.root, "bench", "traffic",
                                      f"{name}.json"))

    def cell(self, name: str) -> Cell:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return Cell(name, int(w["chips"]), w["config"],
                            self.config(w["config"]), w["traffic"],
                            self.traffic(w["traffic"]), self.metrics_of(name))
        raise KeyError(f"no workload {name!r}; have "
                       f"{[w['name'] for w in self.bench['workloads']]}")
