"""Architecture registry: the assigned archs + reduced smoke variants.

Usage:
    from repro.configs import get_config, ARCH_IDS, SHAPES
    cfg  = get_config("grok-1-314b")            # exact assigned dims
    tiny = get_config("grok-1-314b", smoke=True)  # reduced same-family config
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

from repro.configs import (deepseek_v2_lite, gemma3_12b, gemma3_27b,
                           granite_3_8b, grok_1_314b, h2o_danube_3_4b,
                           llava_next_34b, qwen2_moe_a2_7b, rwkv6_1_6b,
                           whisper_medium, zamba2_7b)
from repro.configs.shapes import (SHAPES, ShapeCell, cell_applicable,
                                  input_specs, text_len)
from repro.models.config import ModelConfig, scaled_down

_MODULES = [granite_3_8b, gemma3_27b, h2o_danube_3_4b, gemma3_12b,
            whisper_medium, zamba2_7b, llava_next_34b, rwkv6_1_6b,
            grok_1_314b, qwen2_moe_a2_7b, deepseek_v2_lite]

REGISTRY: Dict[str, ModelConfig] = {m.ARCH_ID: m.CONFIG for m in _MODULES}
ARCH_IDS: List[str] = list(REGISTRY)


def get_config(arch_id: str, smoke: bool = False, **overrides) -> ModelConfig:
    if arch_id not in REGISTRY:
        raise KeyError(f"unknown arch {arch_id!r}; known: {ARCH_IDS}")
    cfg = REGISTRY[arch_id]
    if smoke:
        cfg = scaled_down(cfg)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def for_mode(cfg: ModelConfig, mode: str) -> ModelConfig:
    """Serving stores weights in bf16 (no optimizer → no fp32 master needed);
    training keeps fp32 storage with FSDP sharding."""
    if mode in ("serve", "prefill", "decode"):
        return dataclasses.replace(cfg, param_dtype="bfloat16")
    return cfg


__all__ = ["REGISTRY", "ARCH_IDS", "get_config", "for_mode", "SHAPES",
           "ShapeCell", "cell_applicable", "input_specs", "text_len"]
