"""Model FLOPs of one local SGD step of a dense llama-style decoder,
counted on the logical problem: the matrix multiplications of the forward
pass (projections, MLP, tied unembedding) and causal attention's score and
value products, three times over for forward and backward (PaLM's
convention, arXiv:2204.02311 appendix B: 6 N + 12 L H Q T per token).
Nothing recomputed is counted, and the embedding lookup is not a matmul.
"""
from __future__ import annotations


def matmul_params(c: dict) -> int:
    """Weights that take part in a matrix multiplication per token."""
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    hd = d // c["num_attention_heads"]
    qd = c["num_attention_heads"] * hd
    kvd = c["num_key_value_heads"] * hd
    per_layer = d * qd + 2 * d * kvd + qd * d + 3 * d * f
    return c["num_hidden_layers"] * per_layer + d * v


def step_flops(c: dict, batch: int, seq: int) -> float:
    """FLOPs of one SGD step on ``batch`` sequences of ``seq`` inputs."""
    hd = c["hidden_size"] // c["num_attention_heads"]
    per_token = (6 * matmul_params(c)
                 + 12 * c["num_hidden_layers"] * c["num_attention_heads"]
                 * hd * seq)
    return float(per_token) * batch * seq
