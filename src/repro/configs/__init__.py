"""Architecture registry: one module per assigned architecture.

``get_config(name)`` returns the full assigned config; ``get_smoke_config``
returns the reduced same-family variant used by CPU smoke tests
(<=2 layers, d_model<=512, <=4 experts).
"""
from __future__ import annotations

import importlib
from typing import List

from repro.models.common import ArchConfig

ARCHS: List[str] = [
    "mamba2_780m",
    "deepseek_v2_lite_16b",
    "starcoder2_3b",
    "phi35_moe_42b",
    "gemma3_12b",
    "minitron_8b",
    "zamba2_1p2b",
    "llama32_vision_11b",
    "qwen15_110b",
    "whisper_tiny",
]

# CLI ids (assignment spelling) -> module name
ALIASES = {
    "mamba2-780m": "mamba2_780m",
    "deepseek-v2-lite-16b": "deepseek_v2_lite_16b",
    "starcoder2-3b": "starcoder2_3b",
    "phi3.5-moe-42b-a6.6b": "phi35_moe_42b",
    "gemma3-12b": "gemma3_12b",
    "minitron-8b": "minitron_8b",
    "zamba2-1.2b": "zamba2_1p2b",
    "llama-3.2-vision-11b": "llama32_vision_11b",
    "qwen1.5-110b": "qwen15_110b",
    "whisper-tiny": "whisper_tiny",
    # a benchmark configuration beyond the assigned ten
    "nemotron-3-nano-30b-a3b": "nemotron3_nano_30b",
}


def _module(name: str):
    mod = ALIASES.get(name, name).replace("-", "_").replace(".", "_")
    return importlib.import_module(f"repro.configs.{mod}")


def get_config(name: str) -> ArchConfig:
    return _module(name).CONFIG


def get_smoke_config(name: str) -> ArchConfig:
    return _module(name).SMOKE


def all_arch_ids() -> List[str]:
    """The CLI ids of the assigned architectures (:data:`ARCHS`)."""
    return [k for k, v in ALIASES.items() if v in ARCHS]
