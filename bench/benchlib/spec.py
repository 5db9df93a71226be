"""Find a cell's parts by name from BENCHMARK.json.

A cell (an entry of ``workloads``) names a configuration and a traffic mix;
the configuration's entry names its file of sizes, and the file names its
plain reference (``bench/reference/<reference>.py``) and, by its model's
``family``, the count of a layer's FLOPs (``bench/flops/<family>.py``).
A traffic mix is ``bench/traffic/<traffic>.json``.  A per-layer metric is
read by ``bench/metrics/<name>.py``.  Every part is found under the cell's
root, so adding any of them takes new files and new entries only.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]       # the configuration's file
    traffic_name: str
    traffic: Dict[str, Any]      # the traffic mix's file
    end_to_end: List[Dict[str, Any]]   # the metrics this cell reports
    per_layer: List[Dict[str, Any]]
    root: Path = ROOT            # the checkout its files were found in


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / conf["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name) and m["moves"] in reported]
    return Cell(name, w["chips"], w["config"], config, w["traffic"], traffic,
                e2e, per_layer, Path(root))


_MODULES: Dict[Path, Any] = {}


def load_module(path: Path):
    """The module in the file ``path``, executed once per process."""
    path = Path(path).resolve()
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(
            "bench_" + "_".join(path.parts[-2:]).replace(".", "_")
            .replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def metric_module(name: str, root: Path = ROOT):
    """The module ``bench/metrics/<name>.py``."""
    return load_module(root / "bench" / "metrics" / f"{name}.py")


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    return metric_module(name, root).read
