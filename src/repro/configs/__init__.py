from repro.configs.base import (
    ArchConfig,
    ARCHS,
    get_config,
    list_archs,
    register,
)
from repro.configs.shapes import INPUT_SHAPES, InputShape

# importing the arch modules populates the registry
from repro.configs import (  # noqa: F401
    chameleon_34b,
    granite_20b,
    granite_4_0_h_micro,
    mamba2_370m,
    mixtral_8x7b,
    nemotron_4_15b,
    paper_lstm,
    qwen1_5_4b,
    qwen2_5_32b,
    qwen3_moe_235b_a22b,
    whisper_medium,
    zamba2_2_7b,
)

__all__ = [
    "ARCHS",
    "ArchConfig",
    "INPUT_SHAPES",
    "InputShape",
    "get_config",
    "list_archs",
    "register",
]
