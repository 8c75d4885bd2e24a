"""Decoder-LM assembly for the dense / moe / hybrid / ssm(rwkv) / vlm
families: stacked-layer parameters + ``lax.scan`` over layers (+remat in
training), shared train / prefill / decode entry points.

Layer parameters are STACKED on a leading "layers" axis (init via vmap) so
the whole depth lowers as one ``scan`` — keeping HLO size O(1) in depth,
which is what makes 64-layer x 512-device dry-run compiles tractable
(DESIGN.md section 6 discusses the cost_analysis trip-count correction).
"""
from __future__ import annotations

import contextlib
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import rwkv as RWKV
from repro.models import ssm as SSM

Params = Any


# ---------------------------------------------------------------------------
# per-family block init
# ---------------------------------------------------------------------------


def _init_block(key, cfg: ModelConfig, dtype, dense: bool = False):
    """One layer; ``dense``: a leading dense layer of a MoE model (its MLP
    of width ``dense_d_ff``)."""
    ks = jax.random.split(key, 4)
    if cfg.family == "ssm":  # rwkv6
        tm, tm_s = RWKV.init_rwkv_time_mix(ks[0], cfg, dtype)
        cm, cm_s = RWKV.init_rwkv_channel_mix(ks[1], cfg, dtype)
        params = {"ln1": L.ones_init((cfg.d_model,), jnp.float32), "tm": tm,
                  "ln2": L.ones_init((cfg.d_model,), jnp.float32), "cm": cm}
        specs = {"ln1": ("embed",), "tm": tm_s, "ln2": ("embed",), "cm": cm_s}
        return params, specs

    attn, attn_s = (L.init_mla if cfg.is_mla else L.init_attention)(
        ks[0], cfg, dtype)
    params = {"ln1": L.ones_init((cfg.d_model,), jnp.float32), "attn": attn,
              "ln2": L.ones_init((cfg.d_model,), jnp.float32)}
    specs = {"ln1": ("embed",), "attn": attn_s, "ln2": ("embed",)}

    if cfg.family == "hybrid":
        ssm_p, ssm_s = SSM.init_ssm(ks[2], cfg, dtype)
        params["ssm"] = ssm_p
        specs["ssm"] = ssm_s
        params["ln_attn_o"] = L.ones_init((cfg.d_model,), jnp.float32)
        params["ln_ssm_o"] = L.ones_init((cfg.d_model,), jnp.float32)
        specs["ln_attn_o"] = ("embed",)
        specs["ln_ssm_o"] = ("embed",)

    if cfg.is_moe and not dense:
        moe_p, moe_s = MOE.init_moe(ks[1], cfg, dtype)
        params["moe"] = moe_p
        specs["moe"] = moe_s
    else:
        mlp_p, mlp_s = L.init_mlp(ks[1], cfg, dtype,
                                  width=cfg.dense_d_ff if dense else 0)
        params["mlp"] = mlp_p
        specs["mlp"] = mlp_s
    return params, specs


def _n_dense(cfg: ModelConfig) -> int:
    """Leading dense layers ahead of a MoE stack."""
    return cfg.first_dense_layers if cfg.is_moe else 0


def _init_stack(keys, cfg: ModelConfig, dtype, dense: bool = False):
    params = jax.vmap(lambda k: _init_block(k, cfg, dtype, dense)[0])(keys)
    _, specs = _init_block(keys[0], cfg, dtype, dense)
    specs = jax.tree.map(lambda s: ("layers",) + tuple(s), specs,
                         is_leaf=lambda x: isinstance(x, tuple))
    return params, specs


def init_decoder(key, cfg: ModelConfig):
    """Returns (params, specs) with blocks stacked on a leading layer axis;
    a MoE model's leading dense layers are a stack of their own,
    ``dense_blocks``."""
    dtype = jnp.dtype(cfg.dtype)
    k_emb, k_blocks, k_head, k_proj = jax.random.split(key, 4)

    layer_keys = jax.random.split(k_blocks, cfg.n_layers)
    n_dense = _n_dense(cfg)
    blocks, block_specs = _init_stack(layer_keys[n_dense:], cfg, dtype)

    params = {
        "embed": L.dense_init(k_emb, (cfg.padded_vocab, cfg.d_model), dtype,
                              scale=cfg.d_model ** -0.5),
        "blocks": blocks,
        "norm_f": L.ones_init((cfg.d_model,), jnp.float32),
    }
    specs = {
        "embed": ("vocab", "embed"),
        "blocks": block_specs,
        "norm_f": ("embed",),
    }
    if n_dense:
        params["dense_blocks"], specs["dense_blocks"] = _init_stack(
            layer_keys[:n_dense], cfg, dtype, dense=True)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(
            k_head, (cfg.d_model, cfg.padded_vocab), dtype)
        specs["lm_head"] = ("embed", "vocab")
    if cfg.n_prefix_tokens:
        params["prefix_proj"] = L.dense_init(
            k_proj, (cfg.prefix_dim, cfg.d_model), dtype)
        specs["prefix_proj"] = (None, "embed")
    return params, specs


# ---------------------------------------------------------------------------
# block application — full sequence (train / prefill)
# ---------------------------------------------------------------------------


def _qkv_roped(cfg: ModelConfig, p, x, positions):
    q, k, v = L.qkv_proj(p, x, cfg)
    if cfg.rope_frac > 0:
        rot = int(cfg.head_dim * cfg.rope_frac)
        rot -= rot % 2
        cos, sin = L.rope_angles(positions, rot, cfg.rope_theta)
        q = L.apply_rope(q, cos, sin, cfg.rope_frac)
        k = L.apply_rope(k, cos, sin, cfg.rope_frac)
    return q, k, v


def _attn_seq(cfg, p, x, positions, *, window, prefix_len, collect_kv,
              ring=None):
    """Full-sequence attention sub-block. Returns (out, kv or None).

    ``ring``: optional (mesh, batch_axis, seq_axis) enabling context-
    parallel ring attention (prefill-only beyond-paper path)."""
    if cfg.is_mla:
        q, k, v = L.mla_qkv(p, x, positions, cfg)
    else:
        q, k, v = _qkv_roped(cfg, p, x, positions)
    if ring is not None and window == 0 and prefix_len == 0:
        mesh, bax, sax = ring
        out = L.ring_flash_attention(q, k, v, cfg, mesh, batch_axis=bax,
                                     seq_axis=sax)
    else:
        out = L.flash_attention(q, k, v, cfg, causal=True, window=window,
                                prefix_len=prefix_len)
    kv = (k, v) if collect_kv else None
    return L.out_proj(p, out), kv


def block_seq(cfg: ModelConfig, p, x, positions, *, window=0, prefix_len=0,
              collect_kv=False, states=None, ring=None):
    """One layer over a full sequence.

    Returns (x_out, aux_loss, kv, new_states, stats). ``states`` is the
    recurrent state pytree for ssm/hybrid families (None for pure
    attention); ``stats`` the MoE layer's routing stats ({} elsewhere).
    """
    aux = jnp.zeros((), jnp.float32)
    kv = None
    new_states = None
    stats = {}

    if cfg.family == "ssm":
        tm_in = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        st = states or {}
        b, _, d = x.shape
        tm_shift = st.get("tm_shift",
                          jnp.zeros((b, d), x.dtype))
        wkv = st.get("wkv", jnp.zeros(
            (b, d // cfg.rwkv_head_size, cfg.rwkv_head_size,
             cfg.rwkv_head_size), jnp.float32))
        tm_out, tm_shift_n, wkv_n = RWKV.time_mix(p["tm"], tm_in, cfg,
                                                  tm_shift, wkv)
        x = x + tm_out
        cm_in = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        cm_shift = st.get("cm_shift", jnp.zeros((b, d), x.dtype))
        cm_out, cm_shift_n = RWKV.channel_mix(p["cm"], cm_in, cm_shift)
        x = x + cm_out
        new_states = {"tm_shift": tm_shift_n, "cm_shift": cm_shift_n,
                      "wkv": wkv_n}
        return x, aux, None, new_states, stats

    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    with (jax.named_scope("mla") if cfg.is_mla
          else contextlib.nullcontext()):
        attn_out, kv = _attn_seq(cfg, p["attn"], h, positions,
                                 window=window, prefix_len=prefix_len,
                                 collect_kv=collect_kv, ring=ring)

    if cfg.family == "hybrid":
        st = states or {}
        ssm_out, h_last = SSM.ssm_scan(p["ssm"], h)
        fused = 0.5 * (L.rms_norm(attn_out, p["ln_attn_o"], cfg.norm_eps)
                       + L.rms_norm(ssm_out, p["ln_ssm_o"], cfg.norm_eps))
        x = x + fused
        new_states = {"ssm_h": h_last}
    else:
        x = x + attn_out

    h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        mo, aux, stats = MOE.apply_moe(p["moe"], h2, cfg)
        x = x + mo
    else:
        with jax.named_scope("dense_mlp"):
            x = x + L.apply_mlp(p["mlp"], h2, cfg)
    return x, aux, kv, new_states, stats


# ---------------------------------------------------------------------------
# block application — single-token decode
# ---------------------------------------------------------------------------


def block_decode(cfg: ModelConfig, p, x, cache, pos, *, ring: bool):
    """One layer, one new token. x (B,1,D); cache: this layer's slice.
    Returns (x_out, new_cache)."""
    if cfg.family == "ssm":
        tm_in = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        tm_out, tm_shift, wkv = RWKV.time_mix_step(
            p["tm"], tm_in, cfg, cache["tm_shift"], cache["wkv"])
        x = x + tm_out
        cm_in = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        cm_out, cm_shift = RWKV.channel_mix_step(p["cm"], cm_in,
                                                 cache["cm_shift"])
        x = x + cm_out
        return x, {"tm_shift": tm_shift, "cm_shift": cm_shift, "wkv": wkv}

    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    posv = jnp.full((x.shape[0], 1), pos, jnp.int32)
    if cfg.is_mla:
        q, k, v = L.mla_qkv(p["attn"], h, posv, cfg)
    else:
        q, k, v = _qkv_roped(cfg, p["attn"], h, posv)
    ck, cv, cp = L.cache_write(cache["k"], cache["v"], cache["pos"], k, v,
                               pos, ring)
    window = cfg.long_context_window if ring else 0
    valid = cp >= 0
    if window:
        valid = valid & (cp > pos - window)
    attn = L.decode_attention(q, ck, cv, valid, cfg)
    attn_out = L.out_proj(p["attn"], attn)
    new_cache = {"k": ck, "v": cv, "pos": cp}

    if cfg.family == "hybrid":
        ssm_out, h_new = SSM.ssm_step(p["ssm"], h, cache["ssm_h"])
        fused = 0.5 * (L.rms_norm(attn_out, p["ln_attn_o"], cfg.norm_eps)
                       + L.rms_norm(ssm_out, p["ln_ssm_o"], cfg.norm_eps))
        x = x + fused
        new_cache["ssm_h"] = h_new
    else:
        x = x + attn_out

    h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        x = x + MOE.apply_moe(p["moe"], h2, cfg)[0]
    else:
        x = x + L.apply_mlp(p["mlp"], h2, cfg)
    return x, new_cache


# ---------------------------------------------------------------------------
# full model entry points
# ---------------------------------------------------------------------------


def embed_inputs(cfg: ModelConfig, params, tokens, prefix_embeds=None):
    """tokens (B,S) [+ prefix (B,P,prefix_dim)] -> (x (B,S',D), prefix_len)."""
    x = params["embed"][tokens]
    prefix_len = 0
    if cfg.n_prefix_tokens and prefix_embeds is not None:
        pref = prefix_embeds.astype(x.dtype) @ params["prefix_proj"]
        x = jnp.concatenate([pref, x], axis=1)
        prefix_len = prefix_embeds.shape[1]
    if cfg.family == "encdec" or cfg.rope_frac == 0.0 and cfg.n_heads:
        # NoPE families get additive sinusoidal positions
        s = x.shape[1]
        x = x + L.sinusoid_pos_emb(jnp.arange(s), cfg.d_model)[None].astype(
            x.dtype)
    return x, prefix_len


def unembed(cfg: ModelConfig, params, x):
    x = L.rms_norm(x, params["norm_f"], cfg.norm_eps)
    logits = x @ params["embed"].T if cfg.tie_embeddings \
        else x @ params["lm_head"]
    if cfg.padded_vocab != cfg.vocab_size:
        mask = jnp.where(jnp.arange(cfg.padded_vocab) < cfg.vocab_size,
                         0.0, -1e9).astype(logits.dtype)
        logits = logits + mask
    return logits


def _init_seq_states(cfg: ModelConfig, batch: int, dtype):
    """Zero recurrent states for one layer (stacked later by scan carry)."""
    if cfg.family == "ssm":
        d = cfg.d_model
        h = d // cfg.rwkv_head_size
        return {"tm_shift": jnp.zeros((batch, d), dtype),
                "cm_shift": jnp.zeros((batch, d), dtype),
                "wkv": jnp.zeros((batch, h, cfg.rwkv_head_size,
                                  cfg.rwkv_head_size), jnp.float32)}
    if cfg.family == "hybrid":
        return {"ssm_h": jnp.zeros((batch, cfg.d_model, cfg.ssm_state),
                                   jnp.float32)}
    return None


def layer_pspecs(block_pspecs):
    """Strip the leading stacked-layer axis from a resolved PartitionSpec
    tree (for in-scan-body constraints)."""
    from jax.sharding import PartitionSpec as P
    return jax.tree.map(
        lambda s: P(*tuple(s)[1:]), block_pspecs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))


def decoder_forward(cfg: ModelConfig, params, tokens, prefix_embeds=None, *,
                    window: int = 0, remat: bool = True,
                    collect_cache: bool = False, last_only: bool = False,
                    block_pspecs=None, dense_pspecs=None, act_spec=None,
                    ring=None, stats: bool = False):
    """Full-sequence forward. Returns (logits, aux_loss[, cache][, stats]).

    A MoE model's leading dense layers (``dense_blocks``) run first, each
    stack as one layer scan. ``collect_cache=True`` additionally returns
    the stacked per-layer KV cache / recurrent states (prefill mode).
    ``stats=True`` additionally returns the MoE layers' routing stats
    stacked per layer (``moe.apply_moe``; {} for a model without MoE).

    ``block_pspecs`` / ``dense_pspecs``: resolved PartitionSpec trees for
    the STACKED block params. When given, each scan iteration
    re-constrains its layer slice — without this, the scan-internal
    gradient accumulator for the stacked weights materializes REPLICATED
    (catastrophic for the MoE archs)."""
    x, prefix_len = embed_inputs(cfg, params, tokens, prefix_embeds)
    b, s, _ = x.shape
    positions = jnp.tile(jnp.arange(s)[None], (b, 1))
    if cfg.family == "hybrid" and window == 0:
        window = cfg.long_context_window
    if act_spec is not None:
        x = jax.lax.with_sharding_constraint(x, act_spec)

    def run(x, blocks, pspecs):
        lspecs = layer_pspecs(pspecs) if pspecs is not None else None

        def body(x, layer_p):
            if lspecs is not None:
                layer_p = jax.lax.with_sharding_constraint(layer_p, lspecs)
            if act_spec is not None:
                x = jax.lax.with_sharding_constraint(x, act_spec)
            st = _init_seq_states(cfg, b, x.dtype)
            xo, aux, kv, st_n, layer_stats = block_seq(
                cfg, layer_p, x, positions, window=window,
                prefix_len=prefix_len, collect_kv=collect_cache, states=st,
                ring=ring)
            ys = {}
            if collect_cache:
                if kv is not None:
                    ys["k"], ys["v"] = kv
                    ys["pos"] = positions.astype(jnp.int32)
                if st_n is not None:
                    ys.update(st_n)
            return xo, (aux, ys, layer_stats)

        if remat:
            body = jax.checkpoint(body)
        return jax.lax.scan(body, x, blocks)

    caches = []
    if "dense_blocks" in params:
        x, (_, cache_d, _) = run(x, params["dense_blocks"], dense_pspecs)
        caches.append(cache_d)
    x, (auxs, cache_m, layer_stats) = run(x, params["blocks"], block_pspecs)
    caches.append(cache_m)
    if last_only:
        x = x[:, -1:]
    logits = unembed(cfg, params, x)
    out = (logits, jnp.sum(auxs))
    if collect_cache:
        out += (_concat_layers(caches),)
    if stats:
        out += (layer_stats,)
    return out


def _concat_layers(trees):
    """Per-layer stacks (one per layer scan) as one stack."""
    if len(trees) == 1:
        return trees[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs), *trees)


def decoder_decode(cfg: ModelConfig, params, cache, token, pos, *,
                   ring: bool = False, prefix_embeds=None):
    """One decode step. token (B,) int32; pos: scalar absolute position.
    Returns (logits (B,V), new_cache)."""
    x = params["embed"][token][:, None, :]   # (B,1,D)
    if cfg.family == "encdec" or cfg.rope_frac == 0.0 and cfg.n_heads:
        x = x + L.sinusoid_pos_emb(jnp.array([pos]), cfg.d_model)[None].astype(
            x.dtype)

    def body(x, blk):
        layer_p, layer_cache = blk
        xo, cache_n = block_decode(cfg, layer_p, x, layer_cache, pos,
                                   ring=ring)
        return xo, cache_n

    n_dense = _n_dense(cfg)
    new_caches = []
    if n_dense:
        x, c = jax.lax.scan(body, x, (params["dense_blocks"], jax.tree.map(
            lambda c: c[:n_dense], cache)))
        new_caches.append(c)
        cache = jax.tree.map(lambda c: c[n_dense:], cache)
    x, c = jax.lax.scan(body, x, (params["blocks"], cache))
    new_caches.append(c)
    logits = unembed(cfg, params, x[:, 0, :])
    return logits, _concat_layers(new_caches)


def init_decode_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    """Stacked decode cache for the decoder families."""
    nl = cfg.n_layers
    if cfg.family == "ssm":
        d = cfg.d_model
        h = d // cfg.rwkv_head_size
        return {
            "tm_shift": jnp.zeros((nl, batch, d), dtype),
            "cm_shift": jnp.zeros((nl, batch, d), dtype),
            "wkv": jnp.zeros((nl, batch, h, cfg.rwkv_head_size,
                              cfg.rwkv_head_size), jnp.float32),
        }
    cache = L.init_kv_cache(cfg, batch, max_len, nl, dtype)
    if cfg.family == "hybrid":
        cache["ssm_h"] = jnp.zeros((nl, batch, cfg.d_model, cfg.ssm_state),
                                   jnp.float32)
    return cache


def decode_cache_specs(cfg: ModelConfig):
    if cfg.family == "ssm":
        return {"tm_shift": ("layers", "batch", "embed_act"),
                "cm_shift": ("layers", "batch", "embed_act"),
                "wkv": ("layers", "batch", "rwkv_heads", None, None)}
    specs = dict(L.kv_cache_specs())
    if cfg.family == "hybrid":
        specs["ssm_h"] = ("layers", "batch", "embed_act", None)
    return specs
