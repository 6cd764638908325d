"""Assigned architecture configs (10) + the paper's own eval family."""
from repro.configs.base import ModelConfig, get_config, REGISTRY  # noqa: F401
from repro.configs import (  # noqa: F401
    minitron_8b,
    qwen3_1_7b,
    qwen1_5_4b,
    command_r_plus_104b,
    jamba_1_5_large_398b,
    internvl2_76b,
    mamba2_2_7b,
    whisper_medium,
    granite_moe_1b,
    phi3_5_moe_42b,
    hfa_paper,
    mellum2_12b_a2_5b,
)

ASSIGNED = [
    "minitron-8b",
    "qwen3-1.7b",
    "qwen1.5-4b",
    "command-r-plus-104b",
    "jamba-1.5-large-398b",
    "internvl2-76b",
    "mamba2-2.7b",
    "whisper-medium",
    "granite-moe-1b-a400m",
    "phi3.5-moe-42b-a6.6b",
]
