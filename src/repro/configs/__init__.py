from repro.configs.base import (
    ARCH_ALIASES, ARCH_IDS, ModelConfig, all_configs, get_config,
)

__all__ = [
    "ARCH_ALIASES", "ARCH_IDS", "ModelConfig", "all_configs", "get_config",
]
