"""Mellum2-12B-A2.5B [moe]: 64 routed experts of width 896 (8 per token, no
shared expert), GQA 32:4 heads of 128 at d_model 2304, and a period of
three sliding-window layers (window 1024, default RoPE) and one full layer
(YaRN RoPE, factor 16 over 8192 positions).  The head is not tied to the
embedding.  [hf:JetBrains/Mellum2-12B-A2.5B-Instruct]"""

from repro.configs.base import (AttnKind, ModelConfig, MoECfg, RopeCfg,
                                register)

SLIDING = AttnKind(window=1024, rope=RopeCfg(theta=500_000.0))
FULL = AttnKind(window=0, rope=RopeCfg(
    theta=500_000.0, factor=16.0, original_max=8192, beta_fast=32.0,
    beta_slow=1.0, attention_factor=1.2772588722239782))


@register("mellum2-12b-a2.5b")
def mellum2_12b_a2_5b() -> ModelConfig:
    return ModelConfig(
        name="mellum2-12b-a2.5b",
        family="moe",
        n_layers=28,
        d_model=2304,
        n_heads=32,
        n_kv_heads=4,
        d_ff=896,                 # per-expert hidden
        vocab=98_304,
        d_head=128,
        moe=MoECfg(n_experts=64, top_k=8, d_expert=896, norm_topk_prob=True),
        attn_pattern=(SLIDING, SLIDING, SLIDING, FULL),
        rope_base=500_000.0,
        tie_embeddings=False,
        norm_eps=1e-6,
    )
