"""Plain reference of a llama-architecture decoder (SmolLM,
HuggingFaceTB/SmolLM-135M): token embedding, ``num_hidden_layers`` blocks
of RMSNorm -> grouped-query causal attention with rotary positions (the
rotate-half convention) -> residual -> RMSNorm -> SwiGLU MLP -> residual,
a final RMSNorm and the tied unembedding; next-token cross-entropy; plain
SGD. Written in straightforward ``jax.numpy`` with no kernels, cache or
batching tricks. It imports nothing of the system under test.

The weights are a nested dict whose names the benchmark hands to the
program as well (``init_params``): ``embed`` (V, d); ``blocks`` holding
each layer's ``ln1``, ``attn`` {``wq``, ``wk``, ``wv``, ``wo``}, ``ln2``
and ``mlp`` {``wi`` (up), ``wg`` (gate), ``wo`` (down)} stacked on a
leading layer axis; ``norm_f``.

``dtype`` is the precision of the whole computation: float32 with every
matrix product at ``highest`` precision as the reference, bfloat16 (weights
and arithmetic) for the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def shapes(c: dict) -> dict:
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    hd = d // c["num_attention_heads"]
    qd, kvd = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    L = c["num_hidden_layers"]
    return {"embed": (v, d),
            "blocks": {"ln1": (L, d), "ln2": (L, d),
                       "attn": {"wq": (L, d, qd), "wk": (L, d, kvd),
                                "wv": (L, d, kvd), "wo": (L, qd, d)},
                       "mlp": {"wi": (L, d, f), "wg": (L, d, f),
                               "wo": (L, f, d)}},
            "norm_f": (d,)}


def init_params(key, c: dict, dtype=jnp.float32):
    """Random weights from ``key``: normal(0, initializer_range) matrices,
    unit RMSNorm scales. One jitted call makes them all on the device."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(c), is_leaf=lambda x: isinstance(x, tuple))
    scales = [path[-1].key in ("ln1", "ln2", "norm_f") for path, _ in leaves]
    std = c["initializer_range"]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            jnp.ones(shp, dtype) if scale
            else (std * jax.random.normal(k, shp)).astype(dtype)
            for k, (_, shp), scale in zip(keys, leaves, scales)])

    return make(key)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, theta):
    """Rotate-half rotary embedding over (B, S, H, hd)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang).astype(x.dtype)[None, :, None, :]
    sin = jnp.sin(ang).astype(x.dtype)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def forward(params, tokens, c: dict):
    """tokens (B, S) -> logits (B, S, V)."""
    h_q, h_kv = c["num_attention_heads"], c["num_key_value_heads"]
    d = c["hidden_size"]
    hd = d // h_q
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    x = params["embed"][tokens]
    b, s, _ = x.shape
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        h = _rms(x, p["ln1"], eps)
        q = (h @ p["attn"]["wq"]).reshape(b, s, h_q, hd)
        k = (h @ p["attn"]["wk"]).reshape(b, s, h_kv, hd)
        v = (h @ p["attn"]["wv"]).reshape(b, s, h_kv, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, h_q // h_kv, axis=2)
        v = jnp.repeat(v, h_q // h_kv, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
            jnp.asarray(hd, x.dtype))
        sc = jnp.where(causal, sc, -jnp.inf)
        att = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, h_q * hd)
        x = x + o @ p["attn"]["wo"]
        h = _rms(x, p["ln2"], eps)
        m = jax.nn.silu(h @ p["mlp"]["wg"]) * (h @ p["mlp"]["wi"])
        return x + m @ p["mlp"]["wo"], None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = _rms(x, params["norm_f"], eps)
    return x @ params["embed"].T


def loss(params, tokens, c: dict):
    """Mean next-token cross-entropy of (B, S+1) token rows, in fp32."""
    logits = forward(params, tokens[:, :-1], c).astype(jnp.float32)
    labels = tokens[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


@functools.partial(jax.jit, static_argnames=("cfg", "lr"))
def sgd_step(params, tokens, *, cfg: tuple, lr: float):
    """One SGD step ``p <- p - lr * grad``; returns (params', loss)."""
    c = dict(cfg)
    prec = "highest" if params["embed"].dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        val, grads = jax.value_and_grad(loss)(params, tokens, c)
        new = jax.tree.map(lambda p, g: (p - lr * g).astype(p.dtype),
                           params, grads)
    return new, val


def _cfg(c: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str))))


@functools.partial(jax.jit, static_argnames=("cfg",))
def grad(params, tokens, *, cfg: tuple):
    """The loss gradient at ``params`` on one batch."""
    prec = "highest" if params["embed"].dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        return jax.grad(loss)(params, tokens, dict(cfg))


def local_update(params, batches, c: dict, lr: float):
    """Local SGD over ``batches`` from ``params`` -> (fp32 delta, mean
    loss)."""
    cfg = _cfg(c)
    p, losses = params, []
    for tok in batches:
        p, val = sgd_step(p, jnp.asarray(tok), cfg=cfg, lr=lr)
        losses.append(val)
    delta = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                         - b.astype(jnp.float32), p, params)
    mean = float(jnp.mean(jnp.stack(losses))) if losses else 0.0
    return delta, mean
