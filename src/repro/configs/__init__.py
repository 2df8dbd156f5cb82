from repro.configs.base import (
    ARCH_IDS,
    SHAPES,
    LayerKind,
    ModelConfig,
    ShapeConfig,
    get_config,
    reduced,
    runnable_cells,
    serving_config,
)

__all__ = [
    "ARCH_IDS",
    "SHAPES",
    "LayerKind",
    "ModelConfig",
    "ShapeConfig",
    "get_config",
    "reduced",
    "runnable_cells",
    "serving_config",
]
