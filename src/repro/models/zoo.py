"""Model zoo: config -> (init, train_step, prefill_step, serve_step) plus
sharding-spec resolution onto the production mesh.

Sharding policy (DESIGN.md section 3):
  * "model"-type logical axes (heads, mlp, experts, vocab) shard on the
    ``model`` mesh axis whenever divisible, else stay replicated;
  * "embed"-type axes shard over the batch axes when the arch policy enables
    FSDP (the >=16B archs), else replicate;
  * activations/batch shard over ("pod","data");
  * KV caches shard KV-heads on ``model`` when divisible, else the *sequence*
    dim (flash-decoding style — SPMD inserts the partial-softmax collectives).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec as ED
from repro.models import transformer as T

Params = Any


# ---------------------------------------------------------------------------
# per-arch runtime policy
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    fsdp: bool = False
    # gradient-accumulation microbatches per input shape
    microbatches: Any = dataclasses.field(default_factory=dict)

    def micro_for(self, shape_name: str) -> int:
        return self.microbatches.get(shape_name, 1)


POLICIES: dict[str, ShardingPolicy] = {
    "smollm_135m": ShardingPolicy(microbatches={"train_4k": 2}),
    "stablelm_1_6b": ShardingPolicy(microbatches={"train_4k": 4}),
    "chatglm3_6b": ShardingPolicy(microbatches={"train_4k": 8}),
    "paligemma_3b": ShardingPolicy(microbatches={"train_4k": 2}),
    "hymba_1_5b": ShardingPolicy(microbatches={"train_4k": 4}),
    "seamless_m4t_medium": ShardingPolicy(microbatches={"train_4k": 2}),
    "rwkv6_7b": ShardingPolicy(microbatches={"train_4k": 8}),
    "moonshot_v1_16b_a3b": ShardingPolicy(fsdp=True,
                                          microbatches={"train_4k": 8}),
    "llama4_maverick_400b_a17b": ShardingPolicy(
        fsdp=True, microbatches={"train_4k": 16}),
    "grok_1_314b": ShardingPolicy(fsdp=True, microbatches={"train_4k": 16}),
}


def policy_for(cfg: ModelConfig) -> ShardingPolicy:
    return POLICIES.get(cfg.name, ShardingPolicy())


# ---------------------------------------------------------------------------
# logical-axis resolution
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MeshInfo:
    axis_names: tuple        # e.g. ("pod","data","model") or ("data","model")
    axis_sizes: dict         # name -> size

    @property
    def batch_axes(self):
        return tuple(a for a in self.axis_names if a != "model")

    @property
    def model_size(self) -> int:
        return self.axis_sizes.get("model", 1)

    @property
    def batch_size_total(self) -> int:
        out = 1
        for a in self.batch_axes:
            out *= self.axis_sizes[a]
        return out


def _divisible(dim: Optional[int], n: int) -> bool:
    return dim is not None and n > 0 and dim % n == 0


def resolve_specs(spec_tree, cfg: ModelConfig, mesh: MeshInfo,
                  policy: ShardingPolicy, dims_tree=None):
    """Map logical-axis-name tuples to PartitionSpecs.

    ``dims_tree``: matching pytree of shape tuples (used for divisibility
    checks); if None, divisibility is checked from static cfg fields.
    """
    msize = mesh.model_size
    bsize = mesh.batch_size_total
    fsdp_ok = policy.fsdp

    expert_on_model = _divisible(cfg.n_experts, msize)
    kvheads_on_model = _divisible(cfg.n_kv_heads, msize)
    vocab_on_model = _divisible(cfg.padded_vocab, msize)

    def name_to_axis(name, dim=None):
        if name is None:
            return None
        if name == "layers":
            return None
        if name == "batch":
            if not _divisible(dim, bsize):
                return None
            return mesh.batch_axes if len(mesh.batch_axes) > 1 else \
                mesh.batch_axes[0]
        if name in ("qdim", "kvdim", "mlp", "mlp_d", "heads_d", "expert_mlp",
                    "embed2"):
            if name == "expert_mlp" and expert_on_model:
                return None  # experts already consume the model axis
            return "model" if _divisible(dim, msize) else None
        if name == "expert":
            return "model" if expert_on_model else None
        if name == "vocab":
            return "model" if vocab_on_model else None
        if name == "kvheads":
            return "model" if kvheads_on_model else None
        if name == "kvseq":
            if kvheads_on_model:
                return None  # KV heads already consume the model axis
            return "model" if _divisible(dim, msize) else None
        if name == "rwkv_heads":
            return "model" if _divisible(
                cfg.d_model // max(cfg.rwkv_head_size, 1), msize) else None
        if name == "embed":
            if fsdp_ok and _divisible(dim, bsize):
                return mesh.batch_axes if len(mesh.batch_axes) > 1 else \
                    mesh.batch_axes[0]
            return None
        if name == "embed_act":
            return None
        raise ValueError(f"unknown logical axis {name!r}")

    def resolve_one(names, dims=None):
        axes = []
        for i, nm in enumerate(names):
            d = None if dims is None else dims[i]
            axes.append(name_to_axis(nm, d))
        return P(*axes)

    is_leaf = lambda x: isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)
    if dims_tree is None:
        return jax.tree.map(lambda s: resolve_one(s), spec_tree,
                            is_leaf=is_leaf)
    return jax.tree.map(lambda s, d: resolve_one(s, d), spec_tree, dims_tree,
                        is_leaf=is_leaf)


def specs_with_dims(params_or_shapes, spec_tree, cfg, mesh, policy):
    """Resolve specs using actual array/ShapeDtypeStruct shapes for
    divisibility checks (so e.g. a 9-head q-proj falls back to replicated
    instead of producing an invalid sharding)."""
    is_leaf = lambda x: isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)
    flat_s, treedef = jax.tree.flatten(spec_tree, is_leaf=is_leaf)
    flat_d = [tuple(a.shape) for a in jax.tree.leaves(params_or_shapes)]
    assert len(flat_s) == len(flat_d), (len(flat_s), len(flat_d))
    flat_out = []
    for s, d in zip(flat_s, flat_d):
        assert len(s) == len(d), (s, d)
        flat_out.append(resolve_specs(s, cfg, mesh, policy, dims_tree=d))
    return jax.tree.unflatten(treedef, flat_out)


# ---------------------------------------------------------------------------
# model dispatch
# ---------------------------------------------------------------------------


def init_model(key, cfg: ModelConfig):
    if cfg.family == "encdec":
        return ED.init_encdec(key, cfg)
    return T.init_decoder(key, cfg)


def forward(cfg: ModelConfig, params, batch, *, remat=True, window=0,
            param_pspecs=None, act_spec=None, stats=False):
    """Returns (logits, aux), and with ``stats`` the per-layer MoE routing
    stats of ``transformer.decoder_forward`` ({} without MoE). ``batch``
    dict may carry 'prefix' embeddings
    (vlm) or 'frames' (encdec). ``param_pspecs``: resolved PartitionSpec
    tree matching params (block specs are re-constrained inside the layer
    scan; see transformer.decoder_forward). ``act_spec``: PartitionSpec for
    the (B,S,D) residual stream (pins batch onto the data axes — without it
    GSPMD may replicate activations across data)."""
    if cfg.family == "encdec":
        out = ED.encdec_forward(cfg, params, batch["frames"],
                                batch["tokens"], remat=remat, window=window,
                                block_pspecs=param_pspecs,
                                act_spec=act_spec)
        return out + ({},) if stats else out
    pspecs = param_pspecs or {}
    return T.decoder_forward(cfg, params, batch["tokens"],
                             batch.get("prefix"), remat=remat, window=window,
                             block_pspecs=pspecs.get("blocks"),
                             dense_pspecs=pspecs.get("dense_blocks"),
                             act_spec=act_spec, stats=stats)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------


def token_loss(cfg: ModelConfig, logits, labels, weights=None,
               aux=0.0, aux_coeff=0.01):
    """Per-token next-token CE. ``labels`` (B,S) with -1 = ignore;
    ``weights`` (B,) per-example (client x age) weights.

    For prefix-LM (vlm) the logits cover [prefix + text]; the text-aligned
    slice is taken so logits[:, P + i] predicts labels[:, i].
    """
    if cfg.n_prefix_tokens and cfg.family == "vlm":
        logits = logits[:, cfg.n_prefix_tokens:, :]
    logits = logits.astype(jnp.float32)
    mask = (labels >= 0)
    lab = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    per_ex = jnp.sum(nll, axis=-1) / jnp.maximum(jnp.sum(mask, axis=-1), 1)
    if weights is None:
        loss = jnp.mean(per_ex)
    else:
        w = weights.astype(jnp.float32)
        loss = jnp.sum(per_ex * w) / jnp.maximum(jnp.sum(w), 1e-9)
    return loss + aux_coeff * aux


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------


def effective_microbatches(global_batch: int, micro: int,
                           batch_shards: int) -> int:
    """Largest microbatch count <= ``micro`` such that each microbatch's
    leading dim still divides evenly over the batch mesh axes."""
    micro = max(1, min(micro, global_batch // max(batch_shards, 1)))
    while micro > 1 and (global_batch % micro != 0
                         or (global_batch // micro) % batch_shards != 0):
        micro -= 1
    return micro


def make_train_step(cfg: ModelConfig, *, lr: float = 1e-3,
                    microbatches: int = 1, window: int = 0,
                    remat: bool = True, param_pspecs=None,
                    batch_dim_spec=None, accum_dtype=jnp.float32,
                    act_model_shard: bool = False) -> Callable:
    """Returns step(params, batch) -> (params, metrics).

    Gradient accumulation over ``microbatches`` via lax.scan; the batch's
    leading dim must be divisible. Per-example ``weight`` implements the
    FL age-weighted aggregation (see repro.fl.aggregate).

    ``param_pspecs``/``batch_dim_spec``: optional PartitionSpec trees used to
    pin the grad-accumulation carry and the microbatch slices — scan-carry
    sharding does NOT propagate reliably through SPMD, and an unconstrained
    carry silently replicates the fp32 grads on every device.
    """
    wsc = jax.lax.with_sharding_constraint

    def constrain_grads(g):
        if param_pspecs is None:
            return g
        return wsc(g, param_pspecs)

    def constrain_mb(mb):
        if batch_dim_spec is None:
            return mb
        return jax.tree.map(
            lambda x: wsc(x, P(batch_dim_spec, *([None] * (x.ndim - 1)))),
            mb)

    # act_model_shard: additionally shard the residual stream's hidden dim
    # over the model axis between layers (sequence-parallel analog) — cuts
    # the remat-saved carry by model_size at the cost of a per-layer
    # activation all-gather. §Perf lever.
    act_spec = None
    if batch_dim_spec is not None:
        act_spec = P(batch_dim_spec, None,
                     "model" if act_model_shard else None)

    def loss_fn(params, mb):
        logits, aux = forward(cfg, params, mb, remat=remat, window=window,
                              param_pspecs=param_pspecs, act_spec=act_spec)
        return token_loss(cfg, logits, mb["labels"], mb.get("weight"), aux)

    def step(params, batch):
        if microbatches == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        else:
            def split(x):
                b = x.shape[0]
                return x.reshape((microbatches, b // microbatches)
                                 + x.shape[1:])
            mbs = jax.tree.map(split, batch)

            def accum(carry, mb):
                l, g = jax.value_and_grad(loss_fn)(params, constrain_mb(mb))
                g = constrain_grads(g)
                acc = jax.tree.map(
                    lambda a, b_: a + b_.astype(accum_dtype), carry[1], g)
                return (carry[0] + l, constrain_grads(acc)), None

            zero = (jnp.zeros((), jnp.float32),
                    constrain_grads(jax.tree.map(
                        lambda p: jnp.zeros(p.shape, accum_dtype), params)))
            (loss, grads), _ = jax.lax.scan(accum, zero, mbs)
            loss = loss / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, grads)
        new_params = jax.tree.map(
            lambda p, g: (p.astype(jnp.float32)
                          - lr * g.astype(jnp.float32)).astype(p.dtype),
            params, grads)
        # NOTE: no vdot/ravel here — reshaping a sharded grad to 1-D makes
        # GSPMD all-gather the full fp32 tensor (TBs for the MoE archs).
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree.leaves(grads)))
        return new_params, {"loss": loss, "grad_norm": gnorm}

    return step


def make_prefill_step(cfg: ModelConfig, *, window: int = 0,
                      ring=None) -> Callable:
    """Returns prefill(params, batch) -> (last_logits, cache).
    ``ring``: (mesh, batch_axis, seq_axis) to enable context-parallel ring
    attention (decoder-only families)."""

    def prefill(params, batch):
        if cfg.family == "encdec":
            logits, _, cache = ED.encdec_forward(
                cfg, params, batch["frames"], batch["tokens"], remat=False,
                collect_cache=True, window=window, last_only=True)
        else:
            logits, _, cache = T.decoder_forward(
                cfg, params, batch["tokens"], batch.get("prefix"),
                remat=False, window=window, collect_cache=True,
                last_only=True, ring=ring)
        return logits[:, -1, :], cache

    return prefill


def make_serve_step(cfg: ModelConfig, *, ring: bool = False) -> Callable:
    """Returns serve(params, cache, token, pos) -> (next_token, logits,
    cache). Greedy decode."""

    def serve(params, cache, token, pos):
        if cfg.family == "encdec":
            logits, cache = ED.encdec_decode(cfg, params, cache, token, pos,
                                             ring=ring)
        else:
            logits, cache = T.decoder_decode(cfg, params, cache, token, pos,
                                             ring=ring)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, logits, cache

    return serve


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    dtype = jnp.dtype(cfg.dtype)
    if cfg.family == "encdec":
        return ED.init_encdec_cache(cfg, batch, max_len, dtype)
    return T.init_decode_cache(cfg, batch, max_len, dtype)


def cache_specs(cfg: ModelConfig):
    if cfg.family == "encdec":
        return ED.encdec_cache_specs(cfg)
    return T.decode_cache_specs(cfg)


# ---------------------------------------------------------------------------
# input construction (shapes + example batches)
# ---------------------------------------------------------------------------


def batch_shapes(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """Abstract input shapes for a given (arch, input-shape) pair.

    train/prefill: {tokens, labels, weight [, prefix | frames]}
    decode: {token, pos} + cache built separately.
    """
    b, s = shape.global_batch, shape.seq_len
    dt = jnp.dtype(cfg.dtype)
    if shape.kind in ("train", "prefill"):
        out = {
            "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
            "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
            "weight": jax.ShapeDtypeStruct((b,), jnp.float32),
        }
        if cfg.family == "vlm":
            out["prefix"] = jax.ShapeDtypeStruct(
                (b, cfg.n_prefix_tokens, cfg.prefix_dim), dt)
        if cfg.family == "encdec":
            out["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.n_prefix_tokens, cfg.prefix_dim), dt)
        if shape.kind == "prefill":
            out.pop("labels")
            out.pop("weight")
        return out
    # decode
    return {
        "token": jax.ShapeDtypeStruct((b,), jnp.int32),
    }


def batch_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: MeshInfo) -> dict:
    """PartitionSpecs matching batch_shapes. Batch dim sharded over the batch
    axes when divisible, else replicated."""
    b = shape.global_batch
    bx = mesh.batch_axes
    bsz = mesh.batch_size_total
    baxis = (bx if len(bx) > 1 else bx[0]) if b % bsz == 0 else None
    shapes = batch_shapes(cfg, shape)
    return {k: P(baxis, *([None] * (len(v.shape) - 1)))
            for k, v in shapes.items()}
