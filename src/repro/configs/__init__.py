"""Architecture registry: ``get_config(name)`` / ``--arch <id>``."""
from __future__ import annotations

from repro.configs.base import (
    AttentionConfig,
    EncoderConfig,
    INPUT_SHAPES,
    InputShape,
    ModelConfig,
    MoEConfig,
    SSMConfig,
    reduced,
)

from repro.configs.granite_3_2b import CONFIG as _granite
from repro.configs.whisper_tiny import CONFIG as _whisper
from repro.configs.arctic_480b import CONFIG as _arctic
from repro.configs.qwen2_72b import CONFIG as _qwen2
from repro.configs.deepseek_v2_236b import CONFIG as _deepseek
from repro.configs.deepseek_v2_lite import CONFIG as _deepseek_lite
from repro.configs.hymba_1_5b import CONFIG as _hymba
from repro.configs.rwkv6_7b import CONFIG as _rwkv6
from repro.configs.smollm_360m import CONFIG as _smollm
from repro.configs.internvl2_76b import CONFIG as _internvl
from repro.configs.starcoder2_15b import CONFIG as _starcoder2
from repro.configs.fastmoe_gpt import CONFIG as _fastmoe_gpt, DENSE_BASELINE as _fastmoe_dense
from repro.configs.switch_base import CONFIG as _switch

ARCHS: dict[str, ModelConfig] = {
    c.name: c
    for c in [
        _granite, _whisper, _arctic, _qwen2, _deepseek,
        _hymba, _rwkv6, _smollm, _internvl, _starcoder2,
        _fastmoe_gpt, _fastmoe_dense, _switch, _deepseek_lite,
    ]
}

# The ten assigned architectures (excludes the paper's own GPT configs).
ASSIGNED = [
    "granite-3-2b", "whisper-tiny", "arctic-480b", "qwen2-72b",
    "deepseek-v2-236b", "hymba-1.5b", "rwkv6-7b", "smollm-360m",
    "internvl2-76b", "starcoder2-15b",
]


def get_config(name: str) -> ModelConfig:
    try:
        return ARCHS[name]
    except KeyError:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(ARCHS)}") from None


__all__ = [
    "ARCHS", "ASSIGNED", "AttentionConfig", "EncoderConfig", "INPUT_SHAPES",
    "InputShape", "ModelConfig", "MoEConfig", "SSMConfig", "get_config",
    "reduced",
]
