from repro.config.base import (
    DTYPES,
    INPUT_SHAPES,
    DecodeConfig,
    MeshConfig,
    ModelConfig,
    TrainConfig,
    YarnScaling,
)
from repro.config.registry import (
    get_config,
    get_policy,
    list_archs,
    list_policies,
    register,
)

__all__ = [
    "DTYPES",
    "INPUT_SHAPES",
    "DecodeConfig",
    "MeshConfig",
    "ModelConfig",
    "TrainConfig",
    "YarnScaling",
    "get_config",
    "get_policy",
    "list_archs",
    "list_policies",
    "register",
]
