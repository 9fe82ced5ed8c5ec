"""A cell, found by name: its entry in ``BENCHMARK.json``, its
configuration file, its traffic file and the readers of its metrics.

Nothing here names a cell, a configuration, a traffic mix or a metric:
each is a file of its own, found by the name ``BENCHMARK.json`` gives.
"""

from __future__ import annotations

import copy
import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as fh:
        return json.load(fh)


def merged(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    """``base`` with ``over`` laid on top, nested dicts merged."""
    out = copy.deepcopy(base)
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = merged(out[key], value)
        else:
            out[key] = copy.deepcopy(value)
    return out


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reader_of(folder: str, name: str) -> Callable[[Any], Optional[float]]:
    """The ``read`` function of ``<folder>/<name>.py``: a metric that is
    another metric's copy for one more cell reads through the original."""
    path = os.path.join(BENCH_DIR, folder, name + ".py")
    return load_module(path, f"bench_{folder}_{name.replace('.', '_')}").read


@dataclass
class Metric:
    name: str
    unit: str
    read: Callable[[Any], Optional[float]]


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Metric]
    per_layer: List[Metric]
    run_seconds: int


def _applies(entry: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def _metrics(entries, workload: str, folder: str) -> List[Metric]:
    out = []
    for entry in entries:
        if not _applies(entry, workload):
            continue
        out.append(Metric(entry["name"], entry["unit"], reader_of(folder, entry["name"])))
    return out


def load_cell(workload: str, rehearse: bool = False) -> Cell:
    """The cell ``workload`` as ``BENCHMARK.json`` describes it.  With
    ``rehearse`` the ``rehearse`` group of the configuration and of the
    traffic file is laid over the rest: the tiny sizes of a CPU run."""
    bench = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise SystemExit(
            f"no workload {workload!r} in BENCHMARK.json (has: {sorted(entries)})"
        )
    entry = entries[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(ROOT, configs[entry["config"]]["file"]))
    traffic = _load_json(os.path.join(BENCH_DIR, "traffic", entry["traffic"] + ".json"))
    if rehearse:
        config = merged(config, config.get("rehearse", {}))
        traffic = merged(traffic, traffic.get("rehearse", {}))
    return Cell(
        name=workload,
        chips=entry["chips"],
        config=config,
        traffic=traffic,
        end_to_end=_metrics(bench["end_to_end"], workload, "metrics"),
        per_layer=_metrics(bench["per_layer"], workload, "layers"),
        run_seconds=bench["run_seconds"],
    )


def load_driver(name: str):
    return load_module(os.path.join(BENCH_DIR, "drivers", name + ".py"), f"bench_driver_{name}")
