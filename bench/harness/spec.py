"""Finds the pieces of a cell by name.

``BENCHMARK.json`` at the checkout's root names each cell's configuration and
traffic mix and lists the metrics. Every piece lives in a file of its own,
found by its name, so a new configuration, mix or metric is a new file plus
an entry, never an edit:

- configuration ``<c>``: the ``file`` its entry names (``bench/configs/``);
- traffic mix ``<t>``: ``bench/traffic/<t>.json``;
- metric ``<m>``: ``bench/metrics/<m>.py``, a reader with ``read(run)``
  returning a number, or None where the run holds nothing to read;
- architecture ``<a>``, the ``model_type`` of a model in a configuration:
  ``bench/arch/<a>.py``, the program's side (``program_config(name, hf)``,
  the program's ``ModelConfig``, and ``WEIGHT_RULES``, the weight rules it
  adds to ``model.py``'s common ones), and ``bench/ref/<a>.py``, the plain
  reference's side (``block``, one decoder layer, and ``dims(hf)``, the
  sizes and counts ``work.py`` reads), which imports nothing of the program.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass
from types import ModuleType
from typing import Callable, Dict, List, Optional

#: The checkout's root: ``bench/harness`` is two levels below it.
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, what: str) -> ModuleType:
    """The Python file at ``path``, loaded by its path (not imported as a
    package), so that a checkout brings its own; ``what`` names it in the
    error where the file is missing."""
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {what}: {path} does not exist")
    rel = os.path.splitext(os.path.basename(path))[0]
    name = "bench_" + os.path.basename(os.path.dirname(path)) + "_" + "".join(
        ch if ch.isalnum() else "_" for ch in rel)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # where a dataclass in it looks its module up
    spec.loader.exec_module(mod)
    return mod


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
        return load_module(path, f"reader for metric {metric!r}").read

    # -------------------------------------------------------- architectures
    def architecture(self, hf: Dict, side: str) -> ModuleType:
        """One side of the architecture of the model ``hf`` (a model section
        of a configuration file), found by its ``model_type``: ``side`` is
        ``"arch"`` for the program's, ``"ref"`` for the reference's."""
        kind = hf.get("model_type")
        path = os.path.join(self.root, "bench", side, f"{kind}.py")
        return load_module(path, f"architecture {kind!r} of model "
                                 f"{hf.get('name')!r} ({side} side)")

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
