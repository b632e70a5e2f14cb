"""Architecture registry: ``--arch <id>`` resolution."""

from __future__ import annotations

import importlib
from typing import Dict, List

from repro.models.common import ModelConfig

ARCHS: Dict[str, str] = {
    "llama4-scout-17b-a16e": "repro.configs.llama4_scout_17b_a16e",
    "qwen3-moe-235b-a22b": "repro.configs.qwen3_moe_235b_a22b",
    "qwen3-8b": "repro.configs.qwen3_8b",
    "chatglm3-6b": "repro.configs.chatglm3_6b",
    "gemma3-4b": "repro.configs.gemma3_4b",
    "gemma3-1b": "repro.configs.gemma3_1b",
    "musicgen-large": "repro.configs.musicgen_large",
    "chameleon-34b": "repro.configs.chameleon_34b",
    "xlstm-1.3b": "repro.configs.xlstm_1_3b",
    "recurrentgemma-2b": "repro.configs.recurrentgemma_2b",
}


def list_archs() -> List[str]:
    return sorted(ARCHS)


def get_config(arch: str) -> ModelConfig:
    mod = importlib.import_module(ARCHS[arch])
    cfg = mod.config()
    cfg.validate()
    return cfg


def get_smoke_config(arch: str) -> ModelConfig:
    mod = importlib.import_module(ARCHS[arch])
    cfg = mod.smoke_config()
    cfg.validate()
    return cfg
