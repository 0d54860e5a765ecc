"""Find a cell's files by the names in ``BENCHMARK.json``.

* the configuration: the ``file`` its ``configs`` entry names;
* the traffic mix: ``bench/mixes/<traffic>.json``;
* each metric's reader: ``bench/metrics/<name>.py``, a module with
  ``read(run) -> float | None``.

A cell reports every end-to-end metric that names it under
``workloads`` (or has no ``workloads``), and every per-layer metric that
names it, or that has no ``workloads`` and moves one of the cell's
end-to-end metrics.  Adding a cell, configuration, mix or metric is
adding files and entries; nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Metric:
    name: str
    unit: str
    read: Callable


@dataclass
class Cell:
    name: str
    config_name: str
    config: dict
    traffic: str
    mix: dict
    chips: int
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def reader(name: str, bench_dir: str = BENCH_DIR) -> Callable:
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(m: dict, cell: str, e2e: List[str]) -> bool:
    if "workloads" in m:
        return cell in m["workloads"]
    return "moves" not in m or m["moves"] in e2e


def resolve(workload: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    bench_dir = os.path.join(root, "bench")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload, [])]
    names = [m["name"] for m in e2e]
    per = [m for m in bench["per_layer"] if _applies(m, workload, names)]

    def metric(m: dict) -> Metric:
        return Metric(m["name"], m["unit"], reader(m["name"], bench_dir))

    return Cell(
        name=workload, config_name=cfg["name"],
        config=load_json(os.path.join(root, cfg["file"])),
        traffic=w["traffic"],
        mix=load_json(os.path.join(bench_dir, "mixes",
                                   f"{w['traffic']}.json")),
        chips=int(w["chips"]),
        end_to_end=[metric(m) for m in e2e],
        per_layer=[metric(m) for m in per])
