"""Import side-effect module: populates the architecture registry."""

# The 10 assigned architectures.
import repro.configs.xlstm_1_3b       # noqa: F401
import repro.configs.qwen1_5_32b      # noqa: F401
import repro.configs.granite_3_2b     # noqa: F401
import repro.configs.qwen2_1_5b       # noqa: F401
import repro.configs.qwen2_5_3b       # noqa: F401
import repro.configs.zamba2_2_7b      # noqa: F401
import repro.configs.qwen2_vl_72b     # noqa: F401
import repro.configs.kimi_k2_1t_a32b  # noqa: F401
import repro.configs.grok_1_314b      # noqa: F401
import repro.configs.whisper_base     # noqa: F401

# Beyond-paper GSPN-mixer variant (this work).
import repro.configs.qwen2_1_5b_gspn  # noqa: F401

# Mamba-2 + attention hybrid served beside it (two kinds of cache state).
import repro.configs.granite_4_0_h_micro  # noqa: F401

ASSIGNED = [
    "xlstm-1.3b", "qwen1.5-32b", "granite-3-2b", "qwen2-1.5b",
    "qwen2.5-3b", "zamba2-2.7b", "qwen2-vl-72b", "kimi-k2-1t-a32b",
    "grok-1-314b", "whisper-base",
]

EXTRAS = ["qwen2-1.5b-gspn", "granite-4.0-h-micro"]
