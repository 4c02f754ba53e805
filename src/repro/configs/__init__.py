"""Architecture registry: the assigned configs + the paper's own setups.

Every entry cites its source model card / paper.  ``get_config(name)`` returns
the full-size config; ``get_smoke_config(name)`` the reduced same-family
variant used by CPU smoke tests.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from .base import (  # noqa: F401
    INPUT_SHAPES, ModelConfig, RunConfig, block_period, layer_kinds, reduced,
)

ARCH_IDS: List[str] = [
    "pixtral_12b",
    "qwen3_moe_235b_a22b",
    "falcon_mamba_7b",
    "qwen1_5_110b",
    "whisper_small",
    "smollm_360m",
    "starcoder2_7b",
    "jamba_1_5_large_398b",
    "deepseek_moe_16b",
    "qwen3_14b",
    "deepseek_v2_lite",
]

_ALIASES = {i.replace("_", "-"): i for i in ARCH_IDS}
# hyphenated ids as assigned
_ALIASES.update({
    "pixtral-12b": "pixtral_12b",
    "qwen3-moe-235b-a22b": "qwen3_moe_235b_a22b",
    "falcon-mamba-7b": "falcon_mamba_7b",
    "qwen1.5-110b": "qwen1_5_110b",
    "whisper-small": "whisper_small",
    "smollm-360m": "smollm_360m",
    "starcoder2-7b": "starcoder2_7b",
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "deepseek-moe-16b": "deepseek_moe_16b",
    "qwen3-14b": "qwen3_14b",
    "deepseek-v2-lite": "deepseek_v2_lite",
})


def get_config(name: str) -> ModelConfig:
    mod_name = _ALIASES.get(name, name)
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    return mod.CONFIG


def get_smoke_config(name: str) -> ModelConfig:
    return reduced(get_config(name))


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}
