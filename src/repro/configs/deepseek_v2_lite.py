"""deepseek-v2-lite [moe]: 27L d_model=2048 16H, multi-head latent
attention (kv_lora_rank 512, qk_nope 128 + qk_rope 64, v 128, no query
LoRA, YaRN x40), layer 0 a dense MLP of 10944, layers 1-26 MoE: 64
routed experts of 1408 (softmax, greedy top-6, gates not renormalised)
+ 2 shared experts (one ungated MLP of 2816), vocab=102400, untied
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json]."""

from repro.models.config import ArchConfig, YaRN

FULL = ArchConfig(
    name="deepseek_v2_lite", family="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=10944, moe_d_ff=1408, vocab_size=102400,
    num_experts=64, num_experts_per_tok=6, num_shared_experts=2,
    moe_norm_topk=False, shared_expert_gate=False, first_k_dense=1,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, rope_theta=10_000.0,
    rope_scaling=YaRN(factor=40.0, original_max_position_embeddings=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                      mscale_all_dim=0.707),
)

SMOKE = ArchConfig(
    name="deepseek_v2_lite_smoke", family="moe",
    num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
    d_ff=128, moe_d_ff=32, vocab_size=128,
    num_experts=8, num_experts_per_tok=3, num_shared_experts=2,
    moe_norm_topk=False, shared_expert_gate=False, first_k_dense=1,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, rope_theta=10_000.0,
    rope_scaling=YaRN(factor=40.0, original_max_position_embeddings=16,
                      beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                      mscale_all_dim=0.707),
    dtype="float32",
)
