"""Core transformer layers: norms, RoPE, GQA and multi-head latent
attention (flash-chunked, sliding-window, KV-cache decode), MLPs.

All modules are functional: ``init_*`` returns ``(params, specs)`` where
``specs`` is a pytree of *logical* axis-name tuples mirroring ``params``.
Logical names are resolved to mesh ``PartitionSpec``s by
``repro.models.zoo.resolve_specs`` (see DESIGN.md section 3).

Logical axis vocabulary:
  "embed"   residual-stream dim          -> fsdp axes (or replicated)
  "qdim"    flattened n_heads*head_dim   -> "model"
  "kvdim"   flattened n_kv*head_dim      -> "model"
  "mlp"     FFN hidden                   -> "model"
  "expert"  MoE expert dim               -> "model" (when divisible)
  "vocab"   vocabulary                   -> "model" (when divisible)
  "layers"  stacked-layer leading dim    -> replicated
  None      replicated
"""
from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

Params = Any
Specs = Any

DEFAULT_QCHUNK = 1024
DEFAULT_KVCHUNK = 1024


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype, scale: Optional[float] = None):
    """Truncated-normal fan-in init."""
    if scale is None:
        scale = 1.0 / math.sqrt(shape[0] if len(shape) > 1 else shape[0])
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def zeros_init(shape, dtype):
    return jnp.zeros(shape, dtype)


def ones_init(shape, dtype):
    return jnp.ones(shape, dtype)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------


def rms_norm(x, weight, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# rotary position embedding (partial-dim capable)
# ---------------------------------------------------------------------------


def rope_angles(positions, rot_dim: int, theta: float):
    """positions (...,) int32 -> cos,sin (..., rot_dim//2)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32)
                                / rot_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin, rope_frac: float):
    """x (..., S, H, hd); cos/sin (..., S, rot//2) broadcast over heads.

    Rotates the first ``rope_frac * hd`` dims (pairwise interleave-free
    "half-split" convention), passes the rest through.
    """
    if rope_frac <= 0.0:
        return x
    hd = x.shape[-1]
    rot = int(hd * rope_frac)
    rot -= rot % 2
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(x_rot, 2, axis=-1)
    c = cos[..., None, :].astype(x.dtype)  # add head axis
    s = sin[..., None, :].astype(x.dtype)
    out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return jnp.concatenate([out, x_pass], axis=-1)


def sinusoid_pos_emb(positions, d_model: int):
    """Additive sinusoidal embedding (for rope_frac == 0 families)."""
    half = d_model // 2
    freq = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=jnp.float32)
                   / half)
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# attention parameter block
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, dtype) -> tuple[Params, Specs]:
    d, hd = cfg.d_model, cfg.head_dim
    qd, kvd = cfg.n_heads * hd, cfg.n_kv_heads * hd
    ks = jax.random.split(key, 4)
    params = {
        "wq": dense_init(ks[0], (d, qd), dtype),
        "wk": dense_init(ks[1], (d, kvd), dtype),
        "wv": dense_init(ks[2], (d, kvd), dtype),
        "wo": dense_init(ks[3], (qd, d), dtype, scale=1.0 / math.sqrt(qd)),
    }
    specs = {
        "wq": ("embed", "qdim"),
        "wk": ("embed", "kvdim"),
        "wv": ("embed", "kvdim"),
        "wo": ("qdim", "embed"),
    }
    if cfg.qkv_bias:
        params |= {"bq": zeros_init((qd,), dtype),
                   "bk": zeros_init((kvd,), dtype),
                   "bv": zeros_init((kvd,), dtype)}
        specs |= {"bq": ("qdim",), "bk": ("kvdim",), "bv": ("kvdim",)}
    return params, specs


def qkv_proj(p, x, cfg: ModelConfig):
    """x (B,S,D) -> q (B,S,H,hd), k/v (B,S,KH,hd)."""
    b, s, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def out_proj(p, attn_out):
    b, s = attn_out.shape[:2]
    return attn_out.reshape(b, s, -1) @ p["wo"]


# ---------------------------------------------------------------------------
# multi-head latent attention (MLA, DeepSeek-V2/V3; q_lora_rank null)
# ---------------------------------------------------------------------------

# kv_a_layernorm keeps the RMSNorm module's default eps in the DeepSeek-V3
# modelling code; the block norms take the config's rms_norm_eps
MLA_KV_NORM_EPS = 1e-6


def init_mla(key, cfg: ModelConfig, dtype) -> tuple[Params, Specs]:
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    rope, nope, vd = cfg.qk_rope_head_dim, cfg.qk_nope_head_dim, cfg.v_dim
    ks = jax.random.split(key, 4)
    params = {
        "wq": dense_init(ks[0], (d, h * cfg.head_dim), dtype),
        "wkv_a": dense_init(ks[1], (d, r + rope), dtype),
        "kv_norm": ones_init((r,), jnp.float32),
        "wkv_b": dense_init(ks[2], (r, h * (nope + vd)), dtype),
        "wo": dense_init(ks[3], (h * vd, d), dtype,
                         scale=1.0 / math.sqrt(h * vd)),
    }
    specs = {"wq": ("embed", "qdim"), "wkv_a": ("embed", None),
             "kv_norm": (None,), "wkv_b": (None, "qdim"),
             "wo": ("qdim", "embed")}
    return params, specs


def mla_qkv(p, x, positions, cfg: ModelConfig):
    """x (B,S,D) -> q, k (B,S,H,nope+rope), v (B,S,H,v_dim): queries from
    ``wq``; keys' nope part and values up-projected from the normed latent
    ``c`` of ``wkv_a``, whose rope part ``k_pe`` is one key shared by every
    head; RoPE (rotate-half) on ``q_pe`` and ``k_pe``."""
    b, s, _ = x.shape
    h, r = cfg.n_heads, cfg.kv_lora_rank
    nope, vd = cfg.qk_nope_head_dim, cfg.v_dim
    q = (x @ p["wq"]).reshape(b, s, h, cfg.head_dim)
    kva = x @ p["wkv_a"]
    c = rms_norm(kva[..., :r], p["kv_norm"], MLA_KV_NORM_EPS)
    kv = (c @ p["wkv_b"]).reshape(b, s, h, nope + vd)
    cos, sin = rope_angles(positions, cfg.qk_rope_head_dim, cfg.rope_theta)
    q_pe = apply_rope(q[..., nope:], cos, sin, 1.0)
    k_pe = apply_rope(kva[..., None, r:], cos, sin, 1.0)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (b, s, h, k_pe.shape[-1]))],
        axis=-1)
    return q, k, kv[..., nope:]


# ---------------------------------------------------------------------------
# chunked flash attention (pure jnp; the Pallas twin lives in repro.kernels)
# ---------------------------------------------------------------------------


def _gqa_scores(q, k):
    """q (B,Cq,KH,G,hd), k (B,Ck,KH,hd) -> (B,KH,G,Cq,Ck) fp32."""
    return jnp.einsum("bqkgh,bskh->bkgqs", q, k,
                      preferred_element_type=jnp.float32)


def _softcap(scores, cap: float):
    if cap and cap > 0.0:
        return cap * jnp.tanh(scores / cap)
    return scores


def _pick_chunk(s: int, target: int) -> int:
    """Largest divisor of ``s`` that is <= target (handles prefix-extended
    sequence lengths like 32768 + 256)."""
    c = min(target, s)
    while s % c != 0:
        c -= 1
    return c


def _direct_attention(q, k, v, cfg: ModelConfig, *, causal, window,
                      prefix_len):
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    kh = cfg.n_kv_heads
    g = h // kh
    scale = 1.0 / math.sqrt(hd)
    qf = q.reshape(b, sq, kh, g, hd).astype(jnp.float32) * scale
    s = jnp.einsum("bqkgh,bskh->bkgqs", qf, k.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    s = _softcap(s, cfg.logit_softcap)
    qp = jnp.arange(sq)[:, None] + (skv - sq)   # right-aligned positions
    kp = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        cm = kp <= qp
        if prefix_len > 0:
            cm = cm | (kp < prefix_len)
        mask = mask & cm
    if window and window > 0:
        mask = mask & (kp > qp - window)
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


def flash_attention(q, k, v, cfg: ModelConfig, *, causal: bool = True,
                    window: int = 0, prefix_len: int = 0,
                    q_chunk: int = DEFAULT_QCHUNK,
                    kv_chunk: int = DEFAULT_KVCHUNK):
    """Memory-O(S·chunk) attention with running-softmax accumulation.

    q (B,Sq,H,hd), k/v (B,Skv,KH,hd). Supports causal masking, a
    bidirectional prefix (prefix-LM, ``prefix_len`` tokens attend to and are
    attended by everything before them), and banded sliding windows
    (``window`` > 0: position i attends to j in (i-window, i]).

    Returns (B, Sq, H, hd_v) in q.dtype (``hd_v`` = v's head width).
    """
    b, sq, h, hd = q.shape
    hdv = v.shape[-1]
    skv = k.shape[1]
    kh = cfg.n_kv_heads
    g = h // kh
    if sq * skv <= 256 * 256:
        # toy/smoke shapes: direct masked attention (no scan overhead)
        return _direct_attention(q, k, v, cfg, causal=causal, window=window,
                                 prefix_len=prefix_len)
    q_chunk = _pick_chunk(sq, q_chunk)
    kv_chunk = _pick_chunk(skv, kv_chunk)
    assert sq % q_chunk == 0 and skv % kv_chunk == 0, (sq, q_chunk, skv, kv_chunk)
    nq, nkv = sq // q_chunk, skv // kv_chunk
    scale = 1.0 / math.sqrt(hd)

    qb = q.reshape(b, nq, q_chunk, kh, g, hd).astype(jnp.float32) * scale
    kb = k.reshape(b, nkv, kv_chunk, kh, hd).astype(jnp.float32)
    vb = v.reshape(b, nkv, kv_chunk, kh, hdv).astype(jnp.float32)

    q_pos = jnp.arange(sq).reshape(nq, q_chunk)
    k_pos = jnp.arange(skv).reshape(nkv, kv_chunk)

    def q_block(qi, q_i):
        # q_i (B, Cq, KH, G, hd)
        qp = q_pos[qi]  # (Cq,)

        @functools.partial(jax.checkpoint, prevent_cse=False)
        def kv_step(carry, inp):
            acc, m, l = carry
            k_j, v_j, kp = inp
            s = _gqa_scores(q_i, k_j)          # (B,KH,G,Cq,Ck)
            s = _softcap(s, cfg.logit_softcap)
            mask = jnp.ones((q_chunk, kv_chunk), dtype=bool)
            if causal:
                cm = kp[None, :] <= qp[:, None]
                if prefix_len > 0:
                    cm = cm | (kp[None, :] < prefix_len)
                mask = mask & cm
            if window and window > 0:
                mask = mask & (kp[None, :] > qp[:, None] - window)
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # guard fully-masked rows
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(mask[None, None, None], p, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bskh->bkgqh", p, v_j,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((b, kh, g, q_chunk, hdv), jnp.float32)
        m0 = jnp.full((b, kh, g, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, kh, g, q_chunk), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0),
                                      (jnp.moveaxis(kb, 1, 0),
                                       jnp.moveaxis(vb, 1, 0), k_pos))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        # (B,KH,G,Cq,hd) -> (B,Cq,KH,G,hd)
        return jnp.moveaxis(out, 3, 1)

    q_block_ckpt = functools.partial(jax.checkpoint, prevent_cse=False)(
        q_block)
    outs = jax.lax.map(lambda i: q_block_ckpt(i, qb[:, i]), jnp.arange(nq))
    # (nq, B, Cq, KH, G, hd) -> (B, Sq, H, hd)
    out = jnp.moveaxis(outs, 0, 1).reshape(b, sq, kh, g, hdv)
    return out.reshape(b, sq, h, hdv).astype(q.dtype)


def ring_flash_attention(q, k, v, cfg: ModelConfig, mesh, *,
                         batch_axis="data", seq_axis="model",
                         causal: bool = True):
    """Context-parallel (ring) causal attention for prefill.

    Beyond-paper optimization (EXPERIMENTS.md §Perf, llama4_prefill): when
    q-heads don't divide the model axis, GSPMD splits the head_dim
    contraction and emits an all-reduce per attention block (observed:
    33 TB wire for llama4 x prefill_32k). Instead we shard the SEQUENCE
    over the model axis with shard_map and rotate KV chunks around the ring
    with ppermute — wire drops to (KV bytes x ring hops) per layer and the
    MXU work stays fully local.

    q (B,S,H,hd), k/v (B,S,KH,hd) — S must divide by the seq-axis size.
    Forward-only (prefill); training uses the auto-sharded flash path.
    """
    from jax.sharding import PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    b, s, h, hd = q.shape
    kh = k.shape[2]
    g = h // kh
    n_ring = mesh.shape[seq_axis]
    assert s % n_ring == 0, (s, n_ring)
    scale = 1.0 / math.sqrt(hd)

    def local(qc, kc, vc):
        # qc (b_l, L, H, hd); kc/vc (b_l, L, KH, hd) — local seq chunks
        my = jax.lax.axis_index(seq_axis)
        bl, lq = qc.shape[0], qc.shape[1]
        qf = qc.reshape(bl, lq, kh, g, hd).astype(jnp.float32) * scale
        q_pos = my * lq + jnp.arange(lq)

        def step(carry, i):
            kv_k, kv_v, acc, m, l = carry
            src = (my - i) % n_ring
            k_pos = src * lq + jnp.arange(lq)
            s_ = jnp.einsum("bqkgh,bskh->bkgqs", qf,
                            kv_k.astype(jnp.float32),
                            preferred_element_type=jnp.float32)
            s_ = _softcap(s_, cfg.logit_softcap)
            if causal:
                mask = k_pos[None, :] <= q_pos[:, None]
                s_ = jnp.where(mask[None, None, None], s_, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s_, axis=-1))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s_ - m_safe[..., None])
            p = jnp.where(jnp.isfinite(s_), p, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bskh->bkgqh", p,
                            kv_v.astype(jnp.float32),
                            preferred_element_type=jnp.float32)
            acc = acc * corr[..., None] + pv
            # rotate KV to the next ring neighbour
            perm = [(j, (j + 1) % n_ring) for j in range(n_ring)]
            kv_k = jax.lax.ppermute(kv_k, seq_axis, perm)
            kv_v = jax.lax.ppermute(kv_v, seq_axis, perm)
            return (kv_k, kv_v, acc, m_new, l_new), None

        acc0 = jnp.zeros((bl, kh, g, lq, hd), jnp.float32)
        m0 = jnp.full((bl, kh, g, lq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((bl, kh, g, lq), jnp.float32)
        (_kk, _vv, acc, m, l), _ = jax.lax.scan(
            step, (kc, vc, acc0, m0, l0), jnp.arange(n_ring))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        out = jnp.moveaxis(out, 3, 1).reshape(bl, lq, h, hd)
        return out.astype(qc.dtype)

    spec_q = P(batch_axis, seq_axis, None, None)
    return shard_map(local, mesh=mesh,
                     in_specs=(spec_q, spec_q, spec_q),
                     out_specs=spec_q, check_rep=False)(q, k, v)


def decode_attention(q, k_cache, v_cache, valid_mask, cfg: ModelConfig):
    """Single-token attention against a (ring or linear) KV cache.

    q (B,1,H,hd); k_cache/v_cache (B,S,KH,hd); valid_mask (B,S) bool.
    Returns (B,1,H,hd).
    """
    b, _, h, hd = q.shape
    kh = cfg.n_kv_heads
    g = h // kh
    scale = 1.0 / math.sqrt(hd)
    qf = q.reshape(b, 1, kh, g, hd).astype(jnp.float32) * scale
    s = jnp.einsum("bqkgh,bskh->bkgqs", qf, k_cache.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    s = _softcap(s, cfg.logit_softcap)
    s = jnp.where(valid_mask[:, None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", p, v_cache.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, v_cache.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache (linear + ring-buffer)
# ---------------------------------------------------------------------------


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, n_layers: int,
                  dtype):
    """Stacked-over-layers cache pytree. Positions initialized to -1
    (invalid)."""
    kh, hd = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((n_layers, batch, max_len, kh, hd), dtype),
        "v": jnp.zeros((n_layers, batch, max_len, kh, cfg.v_dim), dtype),
        "pos": jnp.full((n_layers, batch, max_len), -1, jnp.int32),
    }


def kv_cache_specs(ring: bool = False):
    # B on batch axes; flattened kv dim is 4D here -> shard KH*hd jointly via
    # "kvdim" on the concatenated (kh, hd)? Cache kept (B,S,KH,hd); shard KH
    # when divisible else replicate (resolved in zoo.resolve_specs with the
    # "kvheads" logical name).
    return {
        "k": ("layers", "batch", "kvseq", "kvheads", None),
        "v": ("layers", "batch", "kvseq", "kvheads", None),
        "pos": ("layers", "batch", "kvseq"),
    }


def cache_write(cache_k, cache_v, cache_pos, k_new, v_new, pos, ring: bool):
    """Write one token (B,1,KH,hd) at absolute position ``pos`` (scalar int).
    ring=True wraps modulo the cache length."""
    max_len = cache_k.shape[1]
    slot = pos % max_len if ring else jnp.minimum(pos, max_len - 1)
    k = jax.lax.dynamic_update_slice_in_dim(cache_k, k_new, slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache_v, v_new, slot, axis=1)
    b = cache_k.shape[0]
    p = jax.lax.dynamic_update_slice_in_dim(
        cache_pos, jnp.full((b, 1), pos, jnp.int32), slot, axis=1)
    return k, v, p


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, dtype, width: int = 0
             ) -> tuple[Params, Specs]:
    """The MLP of width ``width`` (default ``cfg.d_ff``)."""
    d, f = cfg.d_model, width or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.glu:
        params = {
            "wi": dense_init(ks[0], (d, f), dtype),
            "wg": dense_init(ks[1], (d, f), dtype),
            "wo": dense_init(ks[2], (f, d), dtype, scale=1.0 / math.sqrt(f)),
        }
        specs = {"wi": ("embed", "mlp"), "wg": ("embed", "mlp"),
                 "wo": ("mlp", "embed")}
    else:
        params = {
            "wi": dense_init(ks[0], (d, f), dtype),
            "wo": dense_init(ks[2], (f, d), dtype, scale=1.0 / math.sqrt(f)),
        }
        specs = {"wi": ("embed", "mlp"), "wo": ("mlp", "embed")}
    return params, specs


def apply_mlp(p, x, cfg: ModelConfig):
    if cfg.glu:
        h = jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])
    else:
        h = jax.nn.gelu(x @ p["wi"])
    return h @ p["wo"]
