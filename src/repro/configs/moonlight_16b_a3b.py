"""moonlight-16b-a3b [moe, latent attention]: 27L d_model=2048, 16 MLA
heads (no q LoRA, kv_lora_rank 512, q/k 128 nope + 64 rope, v 128), layer
0 dense (d_ff 11264) then 26 MoE layers of 64 routed experts (width 1408,
top-6, sigmoid scores + selection bias, normalised, x2.446) and 2 shared
experts; vocab 163840 untied; rope_theta 5e4.
[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3]

The sequence-wise balance loss weight (1e-4) is assumed: config.json says
only ``seq_aux: true``.
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    vocab_size=163840,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
                  v_dim=128),
    first_k_dense=1,
    moe=MoEConfig(n_experts=64, top_k=6, expert_ff=1408, scoring="sigmoid",
                  norm_topk=True, routed_scale=2.446, n_shared=2,
                  balance="seq", balance_alpha=1e-4),
    rope_theta=5e4,
    norm_eps=1e-5,
    act="swiglu",
    tie_embeddings=False,
)

REDUCED = ModelConfig(
    name="moonlight-16b-a3b-reduced",
    family="moe",
    n_layers=2,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    d_ff=128,
    vocab_size=256,
    mla=MLAConfig(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_dim=16),
    first_k_dense=1,
    moe=MoEConfig(n_experts=16, top_k=3, expert_ff=32, scoring="sigmoid",
                  norm_topk=True, routed_scale=2.446, n_shared=2,
                  balance="seq", balance_alpha=1e-4),
    held_experts=(0, 4),
    rope_theta=5e4,
    norm_eps=1e-5,
    act="swiglu",
    tie_embeddings=False,
)
