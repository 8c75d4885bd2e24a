"""moonshot-v1-16b-a3b — Moonlight-16B-A3B (DeepSeek-V3 architecture).

[hf:moonshotai/Moonlight-16B-A3B config.json] 27L d_model=2048, MLA with
16 heads (q_lora_rank null, kv_lora_rank 512, qk_nope 128 + qk_rope 64,
v 128); layer 0 dense (SwiGLU 11264, first_k_dense_replace 1); layers
1-26 MoE: 64 routed experts of width 1408, top-6, plus 2 shared experts
(one SwiGLU of 2816); sigmoid scores, noaux_tc selection on score +
e_score_correction_bias with n_group = topk_group = 1, norm_topk_prob,
routed_scaling_factor 2.446; RMSNorm eps 1e-5, rope_theta 50,000 (no
scaling), untied vocabulary 163,840. 15,960,110,208 parameters.

Departures (the same in ``bench/reference/moonlight.py``): the correction
bias starts at zero and is not updated (its update rule is a training
recipe, not the forward pass); no sequence-wise auxiliary loss (the config
gives no coefficient); RoPE in the rotate-half layout (the checkpoint's
interleaved layout is a permutation of the columns of ``wq``/``wkv_a``).
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonshot_v1_16b_a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,
    vocab_size=163_840,
    n_experts=64,
    top_k=6,
    router="sigmoid",
    routed_scale=2.446,
    n_shared_experts=2,
    first_dense_layers=1,
    dense_d_ff=11_264,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    glu=True,
    rope_theta=50_000.0,
)
