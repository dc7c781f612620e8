"""Architecture registry — import every config module to populate it."""
from repro.configs import (  # noqa: F401
    granite_8b,
    jamba_1_5_large_398b,
    llama3_70b,
    mellum2_12b_a2_5b,
    minicpm_2b,
    mixtral_8x22b,
    mixtral_8x7b,
    musicgen_large,
    qwen2_5_14b,
    qwen2_vl_72b,
    qwen3_moe_235b_a22b,
    starcoder2_3b,
    xlstm_125m,
)
