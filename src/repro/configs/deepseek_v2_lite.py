"""deepseek-v2-lite-16b [arXiv:2405.04434;
hf:deepseek-ai/DeepSeek-V2-Lite config.json].

27L, d_model 2048, 16 heads MLA (kv_lora 512, qk 128 nope + 64 rope, v 128,
no q-LoRA, YaRN rope scaling factor 40), vocab 102400, untied head. MoE: 64
routed experts, greedy top-6 by a float32 softmax without renormalizing the
gates, + 2 shared, expert d_ff 1408; layer 0 is a dense MLP (d_ff 10944)."""
from .base import MLAConfig, ModelConfig, MoEConfig, RopeScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,            # qk_nope + qk_rope (nominal; MLA path governs)
    d_ff=1408,
    vocab_size=102400,
    pattern=("global",),
    mlp_kind="swiglu",
    norm="rmsnorm",
    rope_theta=1e4,
    rope_scaling=RopeScaling(factor=40.0, original_max_position=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    mla=MLAConfig(kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
                  v_head_dim=128),
    moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408, n_shared=2,
                  norm_topk=False),
    first_k_dense=1,
    dense_d_ff=10944,
    tie_embeddings=False,
)
