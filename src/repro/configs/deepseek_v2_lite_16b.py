"""deepseek-v2-lite-16b [moe] — MLA + fine-grained MoE.

27L d_model=2048 16H d_ff(expert)=1408 vocab=102400 [arXiv:2405.04434; hf].
MLA kv_lora=512 (no q compression in Lite), qk_nope=128 qk_rope=64 v=128.
MoE: 64 routed experts top-6 (softmax, greedy, no top-k renormalisation)
+ 2 shared, first layer dense (d_ff=10944).  YaRN rope scaling: factor 40
over 4096 original positions, beta_fast 32, beta_slow 1, mscale =
mscale_all_dim = 0.707.
(The assignment note "160 routed" describes V2-full; Lite is 64 routed.)

`EP8_SHARE` ("deepseek-v2-lite") is one chip's share of an 8-way
expert-parallel deployment with data-parallel attention: every layer's
attention, norms, the dense layer and the shared experts, the embedding and
the head, and 8 of each MoE layer's 64 routed experts (experts 0-7; the
router still scores all 64).
"""
from dataclasses import replace

from .base import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,          # MLA: latent-shared; head count for layout only
    d_ff=10944,             # dense-prefix FFN width
    vocab_size=102400,
    moe=MoEConfig(n_experts=64, top_k=6, n_shared=2, d_expert_ff=1408,
                  n_dense_prefix=1),
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0, qk_nope_dim=128,
                  qk_rope_dim=64, v_head_dim=128),
    rope="standard",
    yarn=YarnConfig(factor=40.0, original_max_position=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    norm="rmsnorm",
    act="silu",
)

EP8_SHARE = replace(CONFIG, name="deepseek-v2-lite",
                    moe=replace(CONFIG.moe, n_held=8))
