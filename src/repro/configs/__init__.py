"""Assigned-architecture registry.

Each module defines ``config() -> ModelConfig`` with the exact assigned
numbers.  ``get_config(name)`` resolves an arch id; ``list_archs()`` is the
authoritative cell enumeration used by the dry-run and roofline drivers.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.config import ModelConfig

_ARCH_MODULES = {
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "mamba2-2.7b": "mamba2_2_7b",
    "deepseek-v2-lite-16b": "deepseek_v2_lite_16b",
    "arctic-480b": "arctic_480b",
    "musicgen-large": "musicgen_large",
    "deepseek-67b": "deepseek_67b",
    "tinyllama-1.1b": "tinyllama_1_1b",
    "smollm-360m": "smollm_360m",
    "h2o-danube-1.8b": "h2o_danube_1_8b",
    "phi-3-vision-4.2b": "phi_3_vision_4_2b",
    "mimo-v2-flash": "mimo_v2_flash",
    # the paper's own evaluation model (not part of the assigned 10)
    "paper-llama2-7b": "paper_llama2_7b",
}

ASSIGNED_ARCHS: List[str] = [k for k in _ARCH_MODULES if k != "paper-llama2-7b"]

_cache: Dict[str, ModelConfig] = {}


def get_config(name: str) -> ModelConfig:
    if name not in _cache:
        if name not in _ARCH_MODULES:
            raise KeyError(
                f"unknown arch {name!r}; known: {sorted(_ARCH_MODULES)}")
        mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[name]}")
        _cache[name] = mod.config()
    return _cache[name]


def list_archs() -> List[str]:
    return list(ASSIGNED_ARCHS)
