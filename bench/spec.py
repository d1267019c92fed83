"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration and a traffic mix; each lives in a file of
its own (``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``),
and each metric is a reader of its own (``bench/metrics/<metric>.py``
with a ``read(run)`` function). Adding a cell, a mix, a configuration or
a metric adds files; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    bench: str = BENCH                  # the directory its files are in


def load_spec(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def config_file(spec: Dict, name: str, root: str = ROOT) -> str:
    for c in spec["configs"]:
        if c["name"] == name:
            return os.path.join(root, c["file"])
    raise KeyError(f"no configuration named {name!r} in BENCHMARK.json")


def traffic_file(name: str, bench: str = BENCH) -> str:
    return os.path.join(bench, "traffic", f"{name}.json")


def metric_file(name: str, bench: str = BENCH) -> str:
    return os.path.join(bench, "metrics", f"{name}.py")


def metrics_of(spec: Dict, workload: str, section: str) -> List[Dict]:
    """The metrics of ``section`` that ``workload`` reports. An end-to-end
    metric without ``workloads`` is in every cell; a per-layer metric
    without it is in every cell that reports the metric it moves."""
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if section == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if workload in m.get("workloads", [workload])
            and m["moves"] in names]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    spec = load_spec(root)
    for w in spec["workloads"]:
        if w["name"] == workload:
            break
    else:
        raise KeyError(f"no workload named {workload!r} in BENCHMARK.json")
    bench = os.path.join(root, "bench")
    return Cell(name=workload, chips=int(w["chips"]),
                config=_load_json(config_file(spec, w["config"], root)),
                mix=_load_json(traffic_file(w["traffic"], bench)),
                end_to_end=metrics_of(spec, workload, "end_to_end"),
                per_layer=metrics_of(spec, workload, "per_layer"),
                bench=bench)


def reader(name: str, bench: str = BENCH) -> Callable:
    """The ``read(run)`` function of metric ``name``."""
    path = metric_file(name, bench)
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics: List[Dict], run, bench: str = BENCH
                 ) -> Dict[str, Dict]:
    """``{name: {"value", "unit"}}`` for each metric whose reader found
    something to read; a reader that returns None is left out."""
    out: Dict[str, Dict] = {}
    for m in metrics:
        value: Optional[float] = reader(m["name"], bench)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
