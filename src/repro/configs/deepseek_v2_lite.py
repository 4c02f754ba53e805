"""DeepSeek-V2-Lite at one chip's share of an 8-way expert-parallel layer:
MLA (kv_lora_rank 512, no q LoRA, YaRN ×40) and a leading dense layer, then
fine-grained MoE layers (64 routed experts top-6 softmax, not renormalised,
2 shared, sequence-wise auxiliary loss), dropless.  This chip holds experts
0–7 of each layer and an eighth of the vocabulary; the dense layer and 5
MoE layers are the first pipeline stage (bench/configs/deepseek_v2_lite.json
states the deployment).  [hf:deepseek-ai/DeepSeek-V2-Lite; arXiv:2405.04434]"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=6, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=1408, dense_d_ff=10944, first_k_dense=1, vocab_size=12800,
    rope_theta=1e4, norm_eps=1e-6,
    attn_kind="mla", kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128,
    rope_factor=40.0, rope_original_max_pos=4096, yarn_beta_fast=32.0,
    yarn_beta_slow=1.0, yarn_mscale_all_dim=0.707,
    n_experts=64, experts_per_token=6, n_shared_experts=2,
    n_experts_held=8, expert_first=0, moe_dropless=True,
    norm_topk_prob=False, routed_scaling_factor=1.0, router_aux="seq",
    router_aux_coef=0.001,
    source="hf:deepseek-ai/DeepSeek-V2-Lite (arXiv:2405.04434)",
)
