"""Architecture config registry: ``get_config("qwen3-8b")`` etc.

Every assigned architecture has its own module with ``config()`` (exact
published numbers) and ``smoke_config()`` (reduced same-family variant).
"""

from __future__ import annotations

import importlib

from repro.configs.base import (
    SHAPES,
    SUB_QUADRATIC,
    ShapeSpec,
    shape_applicable,
    smoke_shape,
)
from repro.models.common import ModelConfig

_MODULES = {
    "whisper-small": "repro.configs.whisper_small",
    "qwen3-8b": "repro.configs.qwen3_8b",
    "mistral-nemo-12b": "repro.configs.mistral_nemo_12b",
    "granite-3-2b": "repro.configs.granite_3_2b",
    "mistral-large-123b": "repro.configs.mistral_large_123b",
    "mamba2-1.3b": "repro.configs.mamba2_1_3b",
    "recurrentgemma-9b": "repro.configs.recurrentgemma_9b",
    "mixtral-8x7b": "repro.configs.mixtral_8x7b",
    "olmoe-1b-7b": "repro.configs.olmoe_1b_7b",
    "qwen2-vl-72b": "repro.configs.qwen2_vl_72b",
}

ARCH_IDS = tuple(_MODULES)

# Architectures that run on the gossip-FL trainer's own model
# (``models/mla.py``) rather than through ``build_model``.
FL_ARCH_MODULES = {
    "deepseek-v2-lite": "repro.configs.deepseek_v2_lite",
}
_ALL = {**_MODULES, **FL_ARCH_MODULES}


def _normalize(arch_id: str) -> str:
    a = arch_id.lower().replace("_", "-")
    if a not in _ALL:
        # allow python-module style ids like "mamba2_1_3b"
        for k in _ALL:
            if k.replace("-", "").replace(".", "") == a.replace("-", "").replace(".", ""):
                return k
        raise KeyError(f"unknown arch {arch_id!r}; known: {tuple(_ALL)}")
    return a


def get_config(arch_id: str) -> ModelConfig:
    mod = importlib.import_module(_ALL[_normalize(arch_id)])
    return mod.config()


def get_smoke_config(arch_id: str) -> ModelConfig:
    mod = importlib.import_module(_ALL[_normalize(arch_id)])
    return mod.smoke_config()


__all__ = [
    "ARCH_IDS",
    "SHAPES",
    "SUB_QUADRATIC",
    "ShapeSpec",
    "get_config",
    "get_smoke_config",
    "shape_applicable",
    "smoke_shape",
]
