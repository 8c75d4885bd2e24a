"""Dropless top-k Mixture-of-Experts, with shared experts and an
expert-parallel share (DESIGN.md section 3).

Routing scores every token over all ``n_experts`` routed experts:
``softmax`` takes the top-k of the softmax and renormalises them (with the
Switch load-balance loss); ``sigmoid`` (DeepSeek-V3's ``noaux_tc`` with one
group) chooses the top-k of ``sigmoid(x W_r) + bias`` and weights each
chosen expert by its sigmoid score, normalised over the chosen and scaled
by ``routed_scale``. The router's product runs at ``HIGHEST`` precision.

The layer holds the weights of the routed experts
``[first_held_expert, first_held_expert + n_held)`` only: it routes over
all of them and computes the part of the held ones. No pair is dropped:
every (token, choice) pair routed to a held expert is sorted by expert,
the held experts' SwiGLU runs as grouped products (``jax.lax.ragged_dot``)
over the sorted rows, and the weighted rows are gathered back per token.
The sorted buffer has T * top_k rows, the most the routing allows; the
rows of pairs held elsewhere sit past the last group, are computed by
nobody here, and are zeroed wherever they could enter a result. Shared
experts (one SwiGLU of ``n_shared_experts * d_ff``) see every token.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L


def init_moe(key, cfg: ModelConfig, dtype):
    d, f, e, eh = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.n_held
    ks = jax.random.split(key, 5)
    params = {
        "router": L.dense_init(ks[0], (d, e), jnp.float32),  # fp32 router
        "wi": L.dense_init(ks[1], (eh, d, f), dtype),
        "wg": L.dense_init(ks[2], (eh, d, f), dtype),
        "wo": L.dense_init(ks[3], (eh, f, d), dtype,
                           scale=1.0 / math.sqrt(f)),
    }
    specs = {
        "router": ("embed", None),
        "wi": ("expert", "embed", "expert_mlp"),
        "wg": ("expert", "embed", "expert_mlp"),
        "wo": ("expert", "expert_mlp", "embed"),
    }
    if cfg.router == "sigmoid":
        # e_score_correction_bias: steers the choice only (no gradient)
        params["bias"] = jnp.zeros((e,), jnp.float32)
        specs["bias"] = (None,)
    if cfg.n_shared_experts:
        params["shared"], specs["shared"] = L.init_mlp(
            ks[4], cfg, dtype, width=cfg.n_shared_experts * f)
    return params, specs


def route(p, x, cfg: ModelConfig):
    """x (T, D) -> (weights (T, k) fp32, experts (T, k) int32, aux loss)."""
    k = cfg.top_k
    logits = jnp.dot(x.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, experts = jax.lax.top_k(scores + p["bias"], k)
        w = jnp.take_along_axis(scores, experts, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return w * cfg.routed_scale, experts, jnp.zeros((), jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, experts = jax.lax.top_k(probs, k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    # Switch load-balance loss (eq. 4) on the first choices
    density = jnp.mean(jax.nn.one_hot(experts[:, 0], cfg.n_experts), axis=0)
    aux = jnp.sum(density * jnp.mean(probs, axis=0)) * cfg.n_experts
    return w, experts, aux


def routed_experts(p, x, w, experts, cfg: ModelConfig):
    """The held experts' part: sum over each token's chosen experts held
    here of weight x SwiGLU_e(x). x (T, D); returns ((T, D), counters)."""
    t, d = x.shape
    k, eh = cfg.top_k, cfg.n_held
    local = experts.reshape(-1) - cfg.first_held_expert
    held = (local >= 0) & (local < eh)
    group = jnp.where(held, local, eh)          # eh: held elsewhere
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=eh + 1)[:eh]
    rows_w = jnp.where(held, w.reshape(-1), 0.0)[order]
    # rows past the last group are left undefined by the TPU's grouped
    # product, in the forward and in its input gradient: zero them where
    # they enter and after every product, so that neither the result nor
    # any gradient reads them
    routed = jnp.arange(t * k) < jnp.sum(sizes)

    def grouped(lhs, w):
        out = jax.lax.ragged_dot(lhs, w, sizes)
        return jnp.where(routed[:, None], out, 0)

    xs = jnp.where(routed[:, None], x[order // k], 0)   # sorted by expert
    h = jax.nn.silu(grouped(xs, p["wg"])) * grouped(xs, p["wi"])
    ys = grouped(h, p["wo"]) * rows_w[:, None].astype(h.dtype)
    out = jnp.sum(ys[jnp.argsort(order)].reshape(t, k, d), axis=1)
    counters = {"routed": jnp.sum(sizes), "max_load": jnp.max(sizes),
                "rows": jnp.asarray(t * k, jnp.int32)}
    return out.astype(x.dtype), counters


def apply_moe(p, x, cfg: ModelConfig):
    """x (B,S,D) -> (out (B,S,D), aux loss, stats): ``stats`` holds the
    counters (``routed`` pairs to held experts, the largest held expert's
    ``max_load``, the ``rows`` given to the grouped products) and each
    token's chosen ``experts`` (T, top_k)."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    with jax.named_scope("moe.route"):
        w, experts, aux = route(p, xt, cfg)
    with jax.named_scope("moe.experts"):
        out, stats = routed_experts(p, xt, w, experts, cfg)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            out = out + L.apply_mlp(p["shared"], xt, cfg)
    stats["experts"] = experts
    return out.reshape(b, s, d), aux, stats
