"""moonshot-v1-16b-a3b [moe] — Moonlight-16B-A3B, a DeepSeek-V3 block
[hf:moonshotai/Moonlight-16B-A3B, config.json, model_type deepseek_v3].

27 layers at d_model 2048: layer 0 has a dense SwiGLU MLP of 11,264
(``first_k_dense_replace`` 1), layers 1-26 are MoE.  Attention is
multi-head latent attention (MLA): 16 heads, no query LoRA, a shared
latent of ``kv_lora_rank`` 512, ``qk_nope_head_dim`` 128,
``qk_rope_head_dim`` 64, ``v_head_dim`` 128, rope_theta 50,000.  Each MoE
layer routes over 64 experts of 1,408 and chooses 6 a token by sigmoid
score plus a correction bias (``noaux_tc``, one group); the chosen scores
are renormalised and scaled by 2.446.  Two shared experts of 1,408 run as
one SwiGLU of 2,816.  Vocabulary 163,840, untied head, rms eps 1e-5.
15.96e9 parameters, 2.91e9 active a token.  MoE dispatch is the
merge-path stable kv-sort.
"""

from .base import ModelConfig, MOE

CONFIG = ModelConfig(
    name="moonshot-v1-16b-a3b",
    family=MOE,
    num_layers=27,
    first_k_dense=1,
    dense_ff=11264,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    d_ff=1408,
    vocab_size=163840,
    num_experts=64,
    experts_per_token=6,
    shared_expert_ff=2 * 1408,
    router_scoring="sigmoid",
    routed_scaling=2.446,
    capacity_factor=1.25,
    rope_theta=50_000.0,
    rms_eps=1e-5,
    tie_embeddings=False,
)
