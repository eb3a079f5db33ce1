"""Architecture config registry: ``get_config("<arch-id>")``."""
from .base import (ALL_SHAPES, DECODE_32K, InputShape, LONG_500K, MLAConfig,
                   ModelConfig, MoEConfig, PREFILL_32K, SSMConfig, TRAIN_4K,
                   HybridConfig, YarnConfig, shapes_for)

from . import (chatglm3_6b, command_r_35b, deepseek_v2_lite_16b,
               deepseek_v3_671b, jamba_1_5_large_398b, mamba2_130m,
               musicgen_large, pixtral_12b, stablelm_3b, starcoder2_15b)

REGISTRY = {
    c.name: c
    for c in (*(m.CONFIG for m in (
        jamba_1_5_large_398b, musicgen_large, deepseek_v2_lite_16b,
        deepseek_v3_671b, command_r_35b, stablelm_3b, starcoder2_15b,
        chatglm3_6b, mamba2_130m, pixtral_12b)),
        deepseek_v2_lite_16b.EP8_SHARE)
}

ARCH_IDS = tuple(sorted(REGISTRY))


def get_config(name: str) -> ModelConfig:
    if name not in REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {', '.join(ARCH_IDS)}")
    return REGISTRY[name]


__all__ = ["ALL_SHAPES", "ARCH_IDS", "DECODE_32K", "InputShape", "LONG_500K",
           "MLAConfig", "ModelConfig", "MoEConfig", "PREFILL_32K", "REGISTRY",
           "SSMConfig", "TRAIN_4K", "HybridConfig", "YarnConfig", "get_config",
           "shapes_for"]
