"""Assigned architecture configs.  Importing this package registers every
architecture with repro.core.config's registry (``--arch <id>``)."""
from repro.configs import (  # noqa: F401
    deepseek_v2_236b,
    deepseek_v2_lite,
    dilated_vgg,
    granite_moe_1b_a400m,
    internvl2_2b,
    joyai_llm_flash,
    jamba_1_5_large_398b,
    minitron_8b,
    mistral_large_123b,
    qwen1_5_0_5b,
    qwen2_5_14b,
    rwkv6_1_6b,
    seamless_m4t_large_v2,
)
