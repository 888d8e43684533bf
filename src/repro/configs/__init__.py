"""Architecture registry: the 10 assigned architectures plus the paper's
own two evaluation models (Qwen3-30B-A3B and GPT-OSS-20B).

Every entry cites its source in the config's ``source`` field. Access via
``get_config(name)`` / ``list_configs()``; smoke variants via
``get_smoke_config(name)``.
"""

from __future__ import annotations

import importlib
from typing import List

from repro.models.config import ModelConfig, reduced

_MODULES = {
    "qwen3-moe-235b-a22b": "qwen3_moe_235b_a22b",
    "qwen2-vl-72b": "qwen2_vl_72b",
    "minicpm-2b": "minicpm_2b",
    "stablelm-1.6b": "stablelm_1_6b",
    "recurrentgemma-9b": "recurrentgemma_9b",
    "whisper-base": "whisper_base",
    "yi-34b": "yi_34b",
    "phi4-mini-3.8b": "phi4_mini_3_8b",
    "xlstm-1.3b": "xlstm_1_3b",
    "deepseek-v2-236b": "deepseek_v2_236b",
    # the paper's evaluation models
    "qwen3-30b-a3b": "qwen3_30b_a3b",
    "gpt-oss-20b": "gpt_oss_20b",
    # depth cut to one TPU v5e chip, widths as published
    "qwen3-30b-a3b-1chip": "qwen3_30b_a3b_1chip",
}

ASSIGNED = list(_MODULES)[:10]


def list_configs() -> List[str]:
    return list(_MODULES)


def get_config(name: str) -> ModelConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {list(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    return mod.CONFIG.validate()


def get_smoke_config(name: str) -> ModelConfig:
    return reduced(get_config(name))
