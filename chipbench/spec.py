"""What ``BENCHMARK.json`` names, found on disk.

Everything that belongs to one configuration, traffic mix, traffic kind,
architecture or per-layer metric sits in files of its own, found by name:

* a cell's configuration is the ``file`` its ``configs`` entry names;
* its traffic mix is the data file ``chipbench/traffic/<traffic>.json``,
  whose ``kind`` names the generator ``chipbench/traffic/<kind>.py``;
* a configuration's ``arch`` names its architecture file,
  ``chipbench/arch/<arch>.py``: the layer stack, each block kind's
  parameters and reference, the final norm, the loss terms and the FLOPs;
* each per-layer metric's reader is ``chipbench/metrics/<metric>.py``;
* each checkpoint route's warm-up, where it has one, is
  ``chipbench/warm/<provider>.py``.

Adding one of them is adding files and entries.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
METRICS_DIR = os.path.join(HERE, "metrics")
TRAFFIC_DIR = os.path.join(HERE, "traffic")
ARCH_DIR = os.path.join(HERE, "arch")
WARM_DIR = os.path.join(HERE, "warm")

_MODULES: Dict[str, ModuleType] = {}


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def applies(metric: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_module(directory: str, name: str) -> ModuleType:
    """The module ``<directory>/<name>.py``, loaded once."""
    path = os.path.join(directory, f"{name}.py")
    if path not in _MODULES:
        if not os.path.exists(path):
            raise FileNotFoundError(f"chipbench: no {path}")
        spec = importlib.util.spec_from_file_location(
            "chipbench_" + re.sub(r"\W", "_", os.path.relpath(path, ROOT)),
            path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _MODULES[path] = module
    return _MODULES[path]


def arch(config: Dict[str, Any]) -> ModuleType:
    """The architecture module a configuration's ``arch`` names, in its
    ``arch_dir`` where a ``Cell`` gave it one."""
    return load_module(config.get("arch_dir", ARCH_DIR), config["arch"])


class Cell:
    """One workload with its configuration, traffic and metrics."""

    def __init__(self, bench: Dict[str, Any], workload: str,
                 traffic_dir: str = TRAFFIC_DIR,
                 kinds_dir: str = TRAFFIC_DIR, arch_dir: str = ARCH_DIR):
        by_name = {w["name"]: w for w in bench["workloads"]}
        if workload not in by_name:
            raise KeyError(f"no workload {workload!r}; have "
                           f"{sorted(by_name)}")
        self.workload = by_name[workload]
        self.name = workload
        self.chips = int(self.workload["chips"])
        entry = {c["name"]: c for c in bench["configs"]}[
            self.workload["config"]]
        self.config = load_json(os.path.join(ROOT, entry["file"]))
        if arch_dir != ARCH_DIR:
            self.config["arch_dir"] = arch_dir
        self.traffic = load_json(os.path.join(
            traffic_dir, self.workload["traffic"] + ".json"))
        self.kind = load_module(kinds_dir, self.traffic["kind"])
        self.end_to_end = [m for m in bench["end_to_end"]
                           if applies(m, workload)]
        self.per_layer = [m for m in bench["per_layer"]
                          if applies(m, workload)]


def load_reader(name: str, metrics_dir: str = METRICS_DIR
                ) -> Callable[[Any], Optional[float]]:
    """The ``read(ctx)`` function of a per-layer metric's reader file."""
    return load_module(metrics_dir, name).read


def read_per_layer(metrics: List[Dict[str, Any]], ctx: Any,
                   metrics_dir: str = METRICS_DIR
                   ) -> Dict[str, Dict[str, Any]]:
    """Each metric whose reader finds something to read, with its unit."""
    out = {}
    for m in metrics:
        value = load_reader(m["name"], metrics_dir)(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
