"""Resolve a cell of ``BENCHMARK.json`` by name to its files.

A cell names a configuration and a traffic mix; each metric has a reader.
Everything is found by name, so a later cell, mix or metric is added as
files of its own plus entries in ``BENCHMARK.json``:

* ``configs[].file``           the configuration, as it is run (JSON);
* ``bench/traffic/<mix>.json`` the traffic mix's parameters;
* ``bench/metrics/<name>.py``  the reader of a metric, end to end or per layer.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict] = field(default_factory=list)
    per_layer: List[dict] = field(default_factory=list)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    try:
        wl = next(w for w in bench["workloads"] if w["name"] == name)
    except StopIteration:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json") from None
    conf = next(c for c in bench["configs"] if c["name"] == wl["config"])
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic", wl["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(name=name, chips=wl["chips"], config_name=wl["config"],
                config=config, traffic_name=wl["traffic"], traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


_READERS: Dict[str, object] = {}


def reader(metric: str, root: str = ROOT):
    """The module ``bench/metrics/<metric>.py``: it declares ``UNIT``,
    ``SOURCE`` (and ``LAYER`` and ``MOVES`` for a per-layer metric) and
    ``read(run)``, which returns the metric's value or None where the run
    holds nothing to read."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    if path not in _READERS:
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _READERS[path] = mod
    return _READERS[path]
