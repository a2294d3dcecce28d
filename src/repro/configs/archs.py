"""Import all architecture configs to populate the registry."""

import repro.configs.chatglm3_6b        # noqa: F401
import repro.configs.deepseek_coder_33b  # noqa: F401
import repro.configs.gemma3_27b         # noqa: F401
import repro.configs.granite_moe_3b     # noqa: F401
import repro.configs.internlm2_20b      # noqa: F401
import repro.configs.mamba2_780m        # noqa: F401
import repro.configs.mellum2_12b_a2_5b  # noqa: F401
import repro.configs.pixtral_12b        # noqa: F401
import repro.configs.qwen3_moe_30b      # noqa: F401
import repro.configs.recurrentgemma_2b  # noqa: F401
import repro.configs.whisper_tiny       # noqa: F401
