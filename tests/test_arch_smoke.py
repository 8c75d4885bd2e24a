"""Per-architecture smoke tests (deliverable f): REDUCED variant of each
assigned family — one forward + one train step on CPU, asserting output
shapes and no NaNs; plus decode-path consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import encdec as ED
from repro.models import transformer as T
from repro.models import zoo

B, S = 2, 32


def make_batch(cfg, key):
    ks = jax.random.split(key, 3)
    batch = {
        "tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(ks[1], (B, S), 0, cfg.vocab_size),
        "weight": jnp.ones((B,), jnp.float32),
    }
    if cfg.family == "vlm":
        batch["prefix"] = jax.random.normal(
            ks[2], (B, cfg.n_prefix_tokens, cfg.prefix_dim),
            jnp.dtype(cfg.dtype))
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            ks[2], (B, cfg.n_prefix_tokens, cfg.prefix_dim),
            jnp.dtype(cfg.dtype))
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
class TestArchSmoke:
    def test_reduced_config_limits(self, arch):
        cfg = get_config(arch).reduced()
        assert cfg.n_layers == 2
        assert cfg.d_model <= 512
        assert cfg.n_experts <= 4

    def test_forward_shapes_no_nans(self, arch):
        cfg = get_config(arch).reduced()
        params, specs = zoo.init_model(jax.random.PRNGKey(0), cfg)
        batch = make_batch(cfg, jax.random.PRNGKey(1))
        logits, aux = zoo.forward(cfg, params, batch, remat=False)
        exp_s = S + (cfg.n_prefix_tokens if cfg.family == "vlm" else 0)
        assert logits.shape == (B, exp_s, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
        assert bool(jnp.isfinite(aux))
        # spec tree mirrors param tree
        assert jax.tree.structure(params) == jax.tree.structure(
            specs, is_leaf=lambda x: isinstance(x, tuple))

    def test_train_step_updates_and_finite(self, arch):
        cfg = get_config(arch).reduced()
        params, _ = zoo.init_model(jax.random.PRNGKey(0), cfg)
        batch = make_batch(cfg, jax.random.PRNGKey(1))
        step = jax.jit(zoo.make_train_step(cfg, lr=1e-2, microbatches=2))
        new_params, metrics = step(params, batch)
        assert bool(jnp.isfinite(metrics["loss"]))
        assert bool(jnp.isfinite(metrics["grad_norm"]))
        assert metrics["grad_norm"] > 0
        # at least the embedding moved
        delta = jnp.max(jnp.abs(new_params["embed"].astype(jnp.float32)
                                - params["embed"].astype(jnp.float32)))
        assert float(delta) > 0

    @pytest.mark.slow
    def test_loss_decreases_over_steps(self, arch):
        cfg = get_config(arch).reduced()
        params, _ = zoo.init_model(jax.random.PRNGKey(0), cfg)
        batch = make_batch(cfg, jax.random.PRNGKey(1))
        step = jax.jit(zoo.make_train_step(cfg, lr=5e-2))
        losses = []
        for _ in range(5):
            params, m = step(params, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]


DECODER_ARCHS = [a for a in ARCH_IDS
                 if get_config(a).family not in ("encdec",)]


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["stablelm_1_6b", "chatglm3_6b",
                                  "smollm_135m", "rwkv6_7b", "hymba_1_5b",
                                  "moonshot_v1_16b_a3b"])
def test_decode_matches_forward(arch):
    """Teacher-forced logits == step-by-step decode (the MoE routing is
    dropless, so a token's output does not depend on the others')."""
    cfg = get_config(arch).reduced()
    params, _ = zoo.init_model(jax.random.PRNGKey(1), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, 16), 0,
                              cfg.vocab_size)
    logits_full, _ = T.decoder_forward(cfg, params, toks, remat=False)
    cache = T.init_decode_cache(cfg, B, 16, jnp.dtype(cfg.dtype))
    outs = []
    for i in range(16):
        lg, cache = T.decoder_decode(cfg, params, cache, toks[:, i], i,
                                     ring=False)
        outs.append(lg)
    err = float(jnp.max(jnp.abs(logits_full
                                - jnp.stack(outs, 1)).astype(jnp.float32)))
    scale = float(jnp.max(jnp.abs(logits_full.astype(jnp.float32))))
    assert err <= 3e-4 * max(scale, 1.0)


@pytest.mark.slow
def test_encdec_decode_matches_forward():
    cfg = get_config("seamless_m4t_medium").reduced()
    params, _ = zoo.init_model(jax.random.PRNGKey(1), cfg)
    frames = jax.random.normal(jax.random.PRNGKey(3),
                               (B, cfg.n_prefix_tokens, cfg.prefix_dim))
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, 12), 0,
                              cfg.vocab_size)
    lg_full, _ = ED.encdec_forward(cfg, params, frames, toks, remat=False)
    mem = ED.encode(cfg, params, frames, remat=False)
    cache = ED.init_encdec_cache(cfg, B, 12, jnp.dtype(cfg.dtype))
    ks, vs = [], []
    for l in range(cfg.n_layers):
        lp = jax.tree.map(lambda x: x[l], params["dec_blocks"])
        k, v = ED._cross_kv(cfg, lp["xattn"], mem)
        ks.append(k)
        vs.append(v)
    cache = dict(cache, xk=jnp.stack(ks), xv=jnp.stack(vs))
    outs = []
    for i in range(12):
        lg, cache = ED.encdec_decode(cfg, params, cache, toks[:, i], i)
        outs.append(lg)
    err = float(jnp.max(jnp.abs(lg_full - jnp.stack(outs, 1))))
    assert err < 1e-4 * max(1.0, float(jnp.max(jnp.abs(lg_full))))


@pytest.mark.slow
def test_swa_ring_decode_matches_windowed_forward():
    cfg = dataclasses.replace(get_config("stablelm_1_6b").reduced(),
                              long_context_window=4)
    params, _ = zoo.init_model(jax.random.PRNGKey(1), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, 16), 0,
                              cfg.vocab_size)
    logits_full, _ = T.decoder_forward(cfg, params, toks, remat=False,
                                       window=4)
    cache = T.init_decode_cache(cfg, B, 4, jnp.dtype(cfg.dtype))
    outs = []
    for i in range(16):
        lg, cache = T.decoder_decode(cfg, params, cache, toks[:, i], i,
                                     ring=True)
        outs.append(lg)
    err = float(jnp.max(jnp.abs(logits_full - jnp.stack(outs, 1))))
    assert err < 1e-4 * max(1.0, float(jnp.max(jnp.abs(logits_full))))
