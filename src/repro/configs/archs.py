"""Import every assigned architecture config, populating the registry."""
from repro.configs import (  # noqa: F401
    qwen1_5_32b,
    yi_6b,
    qwen1_5_4b,
    starcoder2_15b,
    mamba2_130m,
    zamba2_1_2b,
    zamba2_7b,
    qwen3_moe_235b,
    mixtral_8x7b,
    whisper_tiny,
    llava_next_mistral_7b,
)
