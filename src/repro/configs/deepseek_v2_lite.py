"""deepseek-v2-lite [moe, mla]: 27L d_model=2048 16H, latent attention
(kv_lora_rank 512, no q_lora; qk 128 + 64 rope, v 128; YaRN x40 over 4096),
layer 0 a dense SwiGLU of 10944, layers 1-26 MoE: 64 routed experts of 1408,
top-6 softmax gates not renormalised, beside 2 shared experts (one SwiGLU of
2 x 1408); vocab 102400, untied.
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]

The shared experts are the dense branch beside the experts
(``dense_residual_ffn``, width ``d_ff_shared``); the dense layer 0 is its
own layer group, so the 26 MoE layers stay one scan.
"""

from repro.configs.base import MLAConfig, ModelConfig, SWMConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="lm",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,
    d_ff=10944,
    vocab=102400,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    yarn=YarnConfig(factor=40.0, original_max_position=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    n_experts=64,
    n_experts_per_token=6,
    d_ff_expert=1408,
    dense_residual_ffn=True,
    d_ff_shared=2816,
    first_dense_layers=1,
    moe_norm_topk=False,
    moe_routed_scale=1.0,
    rope_theta=10_000.0,
    tie_embeddings=False,
    swm=SWMConfig(block_size=128, impl="paper"),
    remat="block",
)

SMOKE = ModelConfig(
    name="deepseek-v2-lite-smoke",
    family="lm",
    n_layers=3,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    head_dim=24,
    d_ff=172,            # not divisible by 8: the dense layer falls to k = 4
    vocab=256,
    mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16),
    yarn=YarnConfig(factor=40.0, original_max_position=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    n_experts=8,
    n_experts_per_token=3,
    d_ff_expert=32,
    dense_residual_ffn=True,
    d_ff_shared=64,
    first_dense_layers=1,
    moe_norm_topk=False,
    rope_theta=10_000.0,
    tie_embeddings=False,
    swm=SWMConfig(block_size=8, impl="paper"),
    remat="none",
    param_dtype="float32",
    compute_dtype="float32",
)
