"""Architecture config registry.

Every assigned architecture exposes:
  full()    -> exact assigned config (used ONLY via lower/compile dry-runs)
  reduced() -> smoke-test variant (<=2 repeat units, d_model<=512, <=4 experts)

``get_config(name)`` / ``get_reduced(name)`` look up by arch id.
"""
from __future__ import annotations

import importlib

from repro.common.registry import Registry

ARCHS = Registry("architecture")

_MODULES = [
    "gemma3_27b",
    "gemma3_12b",
    "llama_3_2_vision_90b",
    "qwen2_5_32b",
    "mamba2_370m",
    "minitron_4b",
    "whisper_large_v3",
    "deepseek_v3_671b",
    "zamba2_7b",
    "arctic_480b",
    "nemotron3_nano",
    "resnet",
]

ARCH_IDS = [
    "gemma3-27b",
    "gemma3-12b",
    "llama-3.2-vision-90b",
    "qwen2.5-32b",
    "mamba2-370m",
    "minitron-4b",
    "whisper-large-v3",
    "deepseek-v3-671b",
    "zamba2-7b",
    "arctic-480b",
    "nemotron3-nano",
]


def _load():
    for m in _MODULES:
        importlib.import_module(f"repro.configs.{m}")


_load()


def get_config(name: str):
    return ARCHS.get(name)["full"]()


def get_reduced(name: str):
    return ARCHS.get(name)["reduced"]()


def arch_ids():
    return list(ARCH_IDS)
