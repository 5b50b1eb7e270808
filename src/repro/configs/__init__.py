"""Config registry: ``get_config(arch_id)`` / ``get_smoke(arch_id)``.

Arch ids use the assignment's dashed names; module files use underscores.
"""
from __future__ import annotations

import importlib
from typing import List

from repro.configs.base import ModelConfig

ARCH_IDS: List[str] = [
    "mixtral-8x22b",
    "moonshot-v1-16b-a3b",
    "mamba2-780m",
    "zamba2-7b",
    "glm4-9b",
    "gemma3-1b",
    "olmo-1b",
    "smollm-360m",
    "seamless-m4t-large-v2",
    "internvl2-2b",
]


def _module(arch_id: str):
    return importlib.import_module(
        "repro.configs." + arch_id.replace("-", "_").replace(".", "_"))


def get_config(arch_id: str) -> ModelConfig:
    return _module(arch_id).CONFIG


def get_smoke(arch_id: str) -> ModelConfig:
    return _module(arch_id).smoke()
