"""--arch registry: id -> (CONFIG, SMOKE)."""

from __future__ import annotations

import importlib
from typing import Dict, Tuple

from repro.configs.base import ModelConfig

__all__ = ["ARCHS", "get_config", "get_smoke"]

ARCHS: Dict[str, str] = {
    "gemma3-27b": "repro.configs.gemma3_27b",
    "qwen3-0.6b": "repro.configs.qwen3_0_6b",
    "deepseek-7b": "repro.configs.deepseek_7b",
    "deepseek-v2-lite": "repro.configs.deepseek_v2_lite",
    "internlm2-20b": "repro.configs.internlm2_20b",
    "arctic-480b": "repro.configs.arctic_480b",
    "qwen3-moe-235b-a22b": "repro.configs.qwen3_moe_235b",
    "paligemma-3b": "repro.configs.paligemma_3b",
    "rwkv6-7b": "repro.configs.rwkv6_7b",
    "seamless-m4t-medium": "repro.configs.seamless_m4t_medium",
    "jamba-v0.1-52b": "repro.configs.jamba_52b",
}

# archs with a sub-quadratic / O(1)-state path that run the long_500k cell
LONG_CONTEXT_ARCHS = {"rwkv6-7b", "jamba-v0.1-52b", "gemma3-27b"}


def _mod(arch: str):
    if arch not in ARCHS:
        raise KeyError(f"unknown arch {arch!r}; choices: {sorted(ARCHS)}")
    return importlib.import_module(ARCHS[arch])


def get_config(arch: str) -> ModelConfig:
    return _mod(arch).CONFIG


def get_smoke(arch: str) -> ModelConfig:
    return _mod(arch).SMOKE
