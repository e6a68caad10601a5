"""Find a cell's configuration, traffic mix and metric readers by name.

Everything is data under the benchmark's directories: ``BENCHMARK.json`` at
the checkout root names each cell as configuration + traffic; the
configuration is the JSON file that ``BENCHMARK.json`` gives it, the traffic
mix is ``bench/traffic/<name>.json`` and each metric is the reader
``bench/metrics/<name>.py``. Adding a cell, a configuration, a mix or a
metric adds files and entries; no code here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable[[object], Optional[float]]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, object]
    traffic: Dict[str, object]
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _load_json(path: str) -> Dict[str, object]:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str, root: str = ROOT) -> Callable[[object], Optional[float]]:
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(f"no metric reader {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _metrics(entries, cell: str, root: str) -> List[Metric]:
    return [Metric(e["name"], e["unit"], load_reader(e["name"], root))
            for e in entries
            if "workloads" not in e or cell in e["workloads"]]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with everything it names."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(root, "bench", "traffic",
                                      w["traffic"] + ".json"))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=_metrics(bench["end_to_end"], name, root),
                per_layer=_metrics(bench["per_layer"], name, root))


def load_peaks(device_kind: str, root: str = ROOT) -> Dict[str, object]:
    """The published peaks of ``device_kind``; an unknown device is an error."""
    peaks = _load_json(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in peaks["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; have {sorted(peaks['devices'])}")
    return peaks["devices"][device_kind]
