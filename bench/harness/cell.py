"""A cell of `BENCHMARK.json`, and the files it is found by.

Everything that belongs to one configuration, traffic mix or metric
lives in a file of its own, found by its name:

- ``bench/configs/<config>.json``: the deployment, as it is run;
- ``bench/traffic/<traffic>.json``: the traffic's parameters, with a
  ``kind`` that names the general generator that reads them,
  ``bench/kinds/<kind>.py``;
- ``bench/limits/<workload>.json``: the limit of each number that the
  cell's correctness check compares;
- ``bench/metrics/<metric>.py``: a reader with ``read(run)`` that gives
  the metric's value, or None where the run has nothing to read.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, NamedTuple, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list          # BENCHMARK.json entries this cell reports
    per_layer: list


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec_path: Optional[str] = None) -> Cell:
    """The workload ``name`` of ``BENCHMARK.json``, with its files."""
    spec = _read(spec_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_file = {c["name"]: c["file"] for c in spec["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_read(os.path.join(ROOT, cfg_file)),
        traffic=_read(os.path.join(BENCH, "traffic", w["traffic"] + ".json")),
        limits=_read(os.path.join(BENCH, "limits", name + ".json")),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
    )


def metric_reader(name: str) -> Callable:
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
