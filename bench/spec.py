"""Resolve a cell of ``BENCHMARK.json`` by name to its files.

A cell names a configuration and a traffic mix; each metric has a reader.
Everything is found by name, so a later cell, mix or metric is added as
files of its own plus entries in ``BENCHMARK.json``:

* ``configs[].file``           the configuration, as it is run (JSON);
* ``bench/arch/<arch>.py``     the configuration's architecture (its
                               ``"arch"`` key): model builder, plain
                               reference and operation counts;
* ``bench/traffic/<mix>.json`` the traffic mix's parameters;
* ``bench/metrics/<name>.py``  the reader of a metric, end to end or per layer.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from types import ModuleType
from typing import Dict, List, Optional

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
    arch: Optional[ModuleType] = None     # bench/arch/<config["arch"]>.py


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
    if "arch" not in config:
        raise KeyError(f"{conf['file']} names no \"arch\": its architecture "
                       f"is the module bench/arch/<arch>.py")
    arch_mod = arch(config["arch"], root)
    with open(os.path.join(root, "bench", "traffic", wl["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(name=name, chips=wl["chips"], config_name=wl["config"],
                config=config, traffic_name=wl["traffic"], traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
                arch=arch_mod)


_MODULES: Dict[str, ModuleType] = {}


def _load(path: str, prefix: str, name: str) -> ModuleType:
    if path not in _MODULES:
        if not os.path.isfile(path):
            raise FileNotFoundError(f"no module {path}")
        spec = importlib.util.spec_from_file_location(
            prefix + name.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def reader(metric: str, root: str = ROOT) -> ModuleType:
    """The module ``bench/metrics/<metric>.py``: it declares ``UNIT``,
    ``SOURCE`` (and ``LAYER`` and ``MOVES`` for a per-layer metric) and
    ``read(run)``, which returns the metric's value or None where the run
    holds nothing to read."""
    return _load(os.path.join(root, "bench", "metrics", metric + ".py"),
                 "bench_metric_", metric)


def arch(name: str, root: str = ROOT) -> ModuleType:
    """The module ``bench/arch/<name>.py``: everything of the benchmark that
    depends on a model's architecture. Each such module exports

    * ``model_config(cfg)``: the program's ``repro.config.ModelConfig``;
    * ``forward(params, cfg, tokens, start, n_out, *, fp8=False)`` and
      ``embed(params, cfg, tokens, width, *, fp8=False)``: the plain
      reference (float32 at the highest precision; ``fp8`` the control),
      importing nothing of the program;
    * ``prefill_flops(cfg, lengths, *, logits_per_seq)`` and
      ``decode_flops(cfg, lengths)``: the forward's operations;
    * ``prefill_attention_calls(cfg, lengths)`` and
      ``decode_attention_calls(cfg, lengths)``: ``(flops, bytes)`` of every
      attention-kernel call of one forward (one decode step), one per
      attention layer.
    """
    return _load(os.path.join(root, "bench", "arch", name + ".py"),
                 "bench_arch_", name)
