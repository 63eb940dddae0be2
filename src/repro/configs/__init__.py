"""Assigned-architecture registry.

Each module exports ``CONFIG`` (full-size, dry-run only) and ``SMOKE_CONFIG``
(reduced, CPU-runnable). ``get_config(name)`` / ``list_archs()`` are the public
entry points used by --arch flags in launch scripts.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.config import ModelConfig
from repro.configs.shapes import SHAPES, get_shape, cells_for_arch  # noqa: F401

ARCHS: List[str] = [
    "whisper_base",
    "rwkv6_1b6",
    "zamba2_7b",
    "qwen3_moe_235b",
    "olmoe_1b_7b",
    "starcoder2_7b",
    "phi3_mini",
    "llama3_8b",
    "granite_3_8b",
    "pixtral_12b",
    "deepseek_v2_lite",
]

# hyphen/dot aliases accepted from CLI
_ALIASES = {
    "whisper-base": "whisper_base",
    "rwkv6-1.6b": "rwkv6_1b6",
    "zamba2-7b": "zamba2_7b",
    "qwen3-moe-235b-a22b": "qwen3_moe_235b",
    "olmoe-1b-7b": "olmoe_1b_7b",
    "starcoder2-7b": "starcoder2_7b",
    "phi3-mini-3.8b": "phi3_mini",
    "llama3-8b": "llama3_8b",
    "granite-3-8b": "granite_3_8b",
    "pixtral-12b": "pixtral_12b",
    "deepseek-v2-lite": "deepseek_v2_lite",
}


def _module(name: str):
    canon = _ALIASES.get(name, name).replace("-", "_").replace(".", "_")
    if canon not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; choose from {ARCHS}")
    return importlib.import_module(f"repro.configs.{canon}")


def get_config(name: str) -> ModelConfig:
    return _module(name).CONFIG


def get_smoke_config(name: str) -> ModelConfig:
    return _module(name).SMOKE_CONFIG


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCHS}
