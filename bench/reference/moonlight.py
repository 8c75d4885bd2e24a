"""Plain reference of Moonlight-16B-A3B (moonshotai/Moonlight-16B-A3B, a
DeepSeek-V3 decoder) on one chip's share of an expert-parallel deployment:
token embedding; ``first_k_dense_replace`` dense layers, then MoE layers,
each RMSNorm -> multi-head latent attention -> residual -> RMSNorm -> MLP
-> residual; a final RMSNorm and the untied head; next-token
cross-entropy; plain SGD. Written in straightforward ``jax.numpy`` with no
kernels, cache, sorting or batching tricks. It imports nothing of the
system under test.

Latent attention (q_lora_rank null): ``q = x W_q`` (H x (nope + rope));
``[c, k_pe] = x W_kva``; ``c = RMSNorm(c)`` (the kv_a_layernorm, eps
1e-6, the DeepSeek-V3 module default); ``[k_nope, v] = c W_kvb``; RoPE on
``q_pe`` and on ``k_pe``, one key shared by every head; causal softmax of
``q . k / sqrt(nope + rope)``; ``o W_o``.

Experts: ``s = sigmoid(x W_r)`` over all ``n_router`` routed experts;
the chosen ``S = top_k(s + bias)``; ``w_e = scale * s_e / sum_S s``; the
layer's output ``sum_{e in S, held} w_e FFN_e(x) + FFN_shared(x)``. The
experts held here are computed densely over every token and weighted by
the routing weights (zero where a token did not choose the expert), so no
dispatch code is shared with the program.

Departures from the published model, identical in the program: the
``e_score_correction_bias`` starts at zero and is not updated (its update
rule is a training recipe); no sequence-wise auxiliary loss (the config
gives no coefficient); RoPE in the rotate-half layout (the checkpoint's
interleaved layout is a permutation of columns of ``W_q`` and ``W_kva``).

The weights are a nested dict whose names the benchmark hands to the
program as well (``init_params``): ``embed`` (V, d); ``dense_blocks`` and
``blocks`` each stacked on a leading layer axis, holding ``ln1``,
``attn`` {``wq``, ``wkv_a``, ``kv_norm``, ``wkv_b``, ``wo``}, ``ln2`` and
``mlp`` {``wi`` (up), ``wg`` (gate), ``wo`` (down)} or ``moe``
{``router``, ``bias``, ``wi``/``wg``/``wo`` (held experts), ``shared``
{``wi``, ``wg``, ``wo``}}; ``norm_f``; ``lm_head`` (d, V).

``dtype`` is the precision of the whole computation: float32 with every
matrix product at ``highest`` precision as the reference, bfloat16 (weights
and arithmetic) for the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

KV_NORM_EPS = 1e-6
NORMS = ("ln1", "ln2", "norm_f", "kv_norm")


def dims(c: dict) -> dict:
    """The sizes the equations use, from a configuration file."""
    sh = c["expert_parallel"]
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "r": c["kv_lora_rank"], "nope": c["qk_nope_head_dim"],
            "rope": c["qk_rope_head_dim"], "vd": c["v_head_dim"],
            "fd": c["intermediate_size"], "f": c["moe_intermediate_size"],
            "fs": c["n_shared_experts"] * c["moe_intermediate_size"],
            "held": c["n_routed_experts"], "first": sh["first_held_expert"],
            "n_router": sh["router_experts"], "k": c["num_experts_per_tok"],
            "scale": c["routed_scaling_factor"], "v": c["vocab_size"],
            "n_dense": c["first_k_dense_replace"],
            "n_moe": c["num_hidden_layers"] - c["first_k_dense_replace"],
            "eps": c["rms_norm_eps"], "theta": c["rope_theta"]}


def shapes(c: dict) -> dict:
    z = dims(c)
    d, h = z["d"], z["h"]

    def attn(n):
        return {"wq": (n, d, h * (z["nope"] + z["rope"])),
                "wkv_a": (n, d, z["r"] + z["rope"]), "kv_norm": (n, z["r"]),
                "wkv_b": (n, z["r"], h * (z["nope"] + z["vd"])),
                "wo": (n, h * z["vd"], d)}

    def mlp(n, f, *lead):
        return {"wi": (n, *lead, d, f), "wg": (n, *lead, d, f),
                "wo": (n, *lead, f, d)}

    nd, nm = z["n_dense"], z["n_moe"]
    return {"embed": (z["v"], d),
            "dense_blocks": {"ln1": (nd, d), "ln2": (nd, d), "attn": attn(nd),
                             "mlp": mlp(nd, z["fd"])},
            "blocks": {"ln1": (nm, d), "ln2": (nm, d), "attn": attn(nm),
                       "moe": {"router": (nm, d, z["n_router"]),
                               "bias": (nm, z["n_router"]),
                               **mlp(nm, z["f"], z["held"]),
                               "shared": mlp(nm, z["fs"])}},
            "norm_f": (d,), "lm_head": (d, z["v"])}


def init_params(key, c: dict, dtype=jnp.float32):
    """Random weights from ``key``: normal(0, initializer_range) matrices,
    unit RMSNorm scales, a zero correction bias. One jitted call makes them
    all on the device."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(c), is_leaf=lambda x: isinstance(x, tuple))
    names = [path[-1].key for path, _ in leaves]
    std = c["initializer_range"]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (_, shp), name in zip(keys, leaves, names):
            if name in NORMS:
                out.append(jnp.ones(shp, dtype))
            elif name == "bias":
                out.append(jnp.zeros(shp, dtype))
            else:
                out.append((std * jax.random.normal(k, shp)).astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return make(key)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, theta):
    """Rotate-half rotary embedding over (B, S, H, n)."""
    s, n = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang).astype(x.dtype)[None, :, None, :]
    sin = jnp.sin(ang).astype(x.dtype)[None, :, None, :]
    x1, x2 = x[..., :n // 2], x[..., n // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(x, p):
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])) @ p["wo"]


def attention(p, x, z):
    """Multi-head latent attention of (B, S, d)."""
    b, s, _ = x.shape
    h, r, nope = z["h"], z["r"], z["nope"]
    q = (x @ p["wq"]).reshape(b, s, h, nope + z["rope"])
    kva = x @ p["wkv_a"]
    c = _rms(kva[..., :r], p["kv_norm"], KV_NORM_EPS)
    kv = (c @ p["wkv_b"]).reshape(b, s, h, nope + z["vd"])
    k_pe = _rope(kva[:, :, None, r:], z["theta"])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], z["theta"])],
                        -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (b, s, h, z["rope"]))], -1)
    v = kv[..., nope:]
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(nope + z["rope"], x.dtype))
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v)
    return o.reshape(b, s, h * z["vd"]) @ p["wo"]


def routing(p, x, z):
    """(weights (..., n_router), chosen (..., k)) of tokens x (..., d):
    each token's weight on every routed expert, zero where not chosen."""
    scores = jax.nn.sigmoid(x @ p["router"])
    _, chosen = jax.lax.top_k(scores + p["bias"], z["k"])
    mask = jnp.sum(jax.nn.one_hot(chosen, z["n_router"], dtype=x.dtype), -2)
    w = scores * mask
    return z["scale"] * w / jnp.sum(w, -1, keepdims=True), chosen


def experts(p, x, z):
    """The held experts' part plus the shared experts, for (B, S, d)."""
    w, _ = routing(p, x, z)
    held = w[..., z["first"]:z["first"] + z["held"]]          # (B, S, Eh)
    up = jnp.einsum("bsd,edf->bsef", x, p["wi"])
    gate = jnp.einsum("bsd,edf->bsef", x, p["wg"])
    out = jnp.einsum("bsef,efd->bsed", jax.nn.silu(gate) * up, p["wo"])
    return jnp.einsum("bse,bsed->bsd", held, out) + _swiglu(x, p["shared"])


def hidden(params, tokens, c: dict):
    """The residual stream after every layer, (B, S, d), and the input of
    each MoE layer's experts (layers, B, S, d)."""
    z = dims(c)
    x = params["embed"][tokens]

    def layer(moe):
        def f(x, p):
            x = x + attention(p["attn"], _rms(x, p["ln1"], z["eps"]), z)
            h = _rms(x, p["ln2"], z["eps"])
            return x + (experts(p["moe"], h, z) if moe
                        else _swiglu(h, p["mlp"])), h
        return f

    x, _ = jax.lax.scan(layer(False), x, params["dense_blocks"])
    return jax.lax.scan(layer(True), x, params["blocks"])


def forward(params, tokens, c: dict):
    """tokens (B, S) -> logits (B, S, V)."""
    x, _ = hidden(params, tokens, c)
    return _rms(x, params["norm_f"], c["rms_norm_eps"]) @ params["lm_head"]


def loss(params, tokens, c: dict):
    """Mean next-token cross-entropy of (B, S+1) token rows, in fp32."""
    logits = forward(params, tokens[:, :-1], c).astype(jnp.float32)
    labels = tokens[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def _prec(params):
    return ("highest" if params["embed"].dtype == jnp.float32
            else "default")


@functools.partial(jax.jit, static_argnames=("cfg",))
def chosen_experts(params, tokens, *, cfg: tuple):
    """Each MoE layer's chosen experts for every input token of (B, S+1)
    rows: (layers, B * S, k)."""
    c = _unfreeze(cfg)
    z = dims(c)
    with jax.default_matmul_precision(_prec(params)):
        _, hs = hidden(params, tokens[:, :-1], c)
        return jax.vmap(lambda p, h: routing(p, h, z)[1])(
            params["blocks"]["moe"], hs).reshape(z["n_moe"], -1, z["k"])


@functools.partial(jax.jit, static_argnames=("cfg", "lr"))
def sgd_step(params, tokens, *, cfg: tuple, lr: float):
    """One SGD step ``p <- p - lr * grad``; returns (params', loss)."""
    c = _unfreeze(cfg)
    with jax.default_matmul_precision(_prec(params)):
        val, grads = jax.value_and_grad(loss)(params, tokens, c)
        new = jax.tree.map(lambda p, g: (p - lr * g).astype(p.dtype),
                           params, grads)
    return new, val


def _cfg(c: dict) -> tuple:
    """The configuration's numbers and strings (and those of its
    ``expert_parallel``) as a static, hashable jit argument."""
    def flat(d):
        return tuple(sorted((k, v) for k, v in d.items()
                            if isinstance(v, (int, float, str))))
    return flat(c) + (("expert_parallel", flat(c["expert_parallel"])),)


def _unfreeze(cfg: tuple) -> dict:
    return {k: dict(v) if k == "expert_parallel" else v for k, v in cfg}


@functools.partial(jax.jit, static_argnames=("cfg",))
def grad(params, tokens, *, cfg: tuple):
    """The loss gradient at ``params`` on one batch."""
    with jax.default_matmul_precision(_prec(params)):
        return jax.grad(loss)(params, tokens, _unfreeze(cfg))


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
