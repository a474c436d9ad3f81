"""Architecture registry: ``get(name)`` returns the exact assigned config."""

from __future__ import annotations

from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig,  # noqa: F401
                                SHAPES, ShapeConfig, YarnConfig)

from repro.configs.musicgen_large import CONFIG as musicgen_large
from repro.configs.chatglm3_6b import CONFIG as chatglm3_6b
from repro.configs.qwen3_32b import CONFIG as qwen3_32b
from repro.configs.yi_6b import CONFIG as yi_6b
from repro.configs.qwen2_72b import CONFIG as qwen2_72b
from repro.configs.qwen2_vl_72b import CONFIG as qwen2_vl_72b
from repro.configs.kimi_k2 import CONFIG as kimi_k2
from repro.configs.qwen3_moe_235b import CONFIG as qwen3_moe_235b
from repro.configs.rwkv6_1b6 import CONFIG as rwkv6_1b6
from repro.configs.recurrentgemma_2b import CONFIG as recurrentgemma_2b
from repro.configs.deepseek_v2_lite import CONFIG as deepseek_v2_lite

ARCHS: dict[str, ModelConfig] = {c.name: c for c in [
    musicgen_large, chatglm3_6b, qwen3_32b, yi_6b, qwen2_72b, qwen2_vl_72b,
    kimi_k2, qwen3_moe_235b, rwkv6_1b6, recurrentgemma_2b, deepseek_v2_lite,
]}
assert len(ARCHS) == 11


def get(name: str) -> ModelConfig:
    return ARCHS[name]
