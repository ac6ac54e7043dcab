from repro.configs.base import (
    MLAConfig, ModelConfig, MoEConfig, SSMConfig, ShapeConfig, TrainConfig,
    YaRN, TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K,
    ALL_SHAPES, SHAPES_BY_NAME, applicable_shapes,
)
from repro.configs.registry import ARCH_IDS, get_config, all_configs, reduced_config

__all__ = [
    "MLAConfig", "ModelConfig", "MoEConfig", "YaRN", "SSMConfig", "ShapeConfig", "TrainConfig",
    "TRAIN_4K", "PREFILL_32K", "DECODE_32K", "LONG_500K",
    "ALL_SHAPES", "SHAPES_BY_NAME", "applicable_shapes",
    "ARCH_IDS", "get_config", "all_configs", "reduced_config",
]
