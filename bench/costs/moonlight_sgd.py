"""Model FLOPs of one local SGD step of Moonlight-16B-A3B on one chip's
share of expert parallelism (``bench/configs/moonlight_16b_a3b.json``),
counted on the logical problem, three times over for forward and backward
(PaLM's convention, arXiv:2204.02311 appendix B): 6 x the matmul weights
each token meets outside the routed experts (latent attention's
projections, the dense layer's MLP, the shared experts, the router and the
head over the vocabulary slice); 6 x H x (qk + v head widths) x positions
per token and layer for attention's score and value products; and 6 x 3 x
d x expert width per (token, choice) pair routed to a held expert, as the
step counts them. Nothing recomputed is counted; the embedding lookup is
not a matmul.
"""
from __future__ import annotations


def attn_params(c: dict) -> int:
    d, h, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (d * h * qk + d * (r + c["qk_rope_head_dim"])
            + r * h * (c["qk_nope_head_dim"] + c["v_head_dim"])
            + h * c["v_head_dim"] * d)


def matmul_params(c: dict) -> int:
    """Weights that take part in a matrix multiplication for every token
    (the routed experts' are counted per routed pair instead)."""
    d = c["hidden_size"]
    n_dense = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_dense
    shared = 3 * d * c["n_shared_experts"] * c["moe_intermediate_size"]
    router = d * c["expert_parallel"]["router_experts"]
    return (c["num_hidden_layers"] * attn_params(c)
            + n_dense * 3 * d * c["intermediate_size"]
            + n_moe * (shared + router) + d * c["vocab_size"])


def routed_pair_flops(c: dict) -> float:
    """Forward and backward FLOPs of one (token, choice) pair in a held
    expert's SwiGLU."""
    return 6.0 * 3 * c["hidden_size"] * c["moe_intermediate_size"]


def step_flops(c: dict, batch: int, seq: int, routed_pairs: float) -> float:
    """FLOPs of one SGD step on ``batch`` sequences of ``seq`` inputs whose
    MoE layers routed ``routed_pairs`` pairs to held experts in all."""
    h = c["num_attention_heads"]
    qkv = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    per_token = (6 * matmul_params(c)
                 + 6 * c["num_hidden_layers"] * h * qkv * seq)
    return float(per_token) * batch * seq + routed_pair_flops(c) * routed_pairs
