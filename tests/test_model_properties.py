"""Property tests on model-layer invariants (hypothesis)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.configs import get_config
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import zoo


class TestRoPE:
    @given(st.integers(0, 500), st.integers(0, 500), st.integers(1, 64))
    @settings(max_examples=30, deadline=None)
    def test_relative_position_invariance(self, p1, delta, seed):
        """RoPE dot products depend only on relative positions."""
        hd = 32
        key = jax.random.PRNGKey(seed)
        q = jax.random.normal(key, (1, 1, 1, hd))
        k = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, 1, hd))

        def dot_at(pq, pk):
            cq, sq = L.rope_angles(jnp.array([[pq]]), hd, 10_000.0)
            ck, sk = L.rope_angles(jnp.array([[pk]]), hd, 10_000.0)
            qr = L.apply_rope(q, cq, sq, 1.0)
            kr = L.apply_rope(k, ck, sk, 1.0)
            return float(jnp.sum(qr * kr))

        d1 = dot_at(p1, p1 + delta)
        d2 = dot_at(p1 + 37, p1 + 37 + delta)
        assert d1 == pytest.approx(d2, abs=1e-3)

    def test_partial_rope_passthrough(self):
        """rope_frac < 1: the tail of the head dim is untouched."""
        hd, rot_frac = 32, 0.5
        x = jnp.ones((1, 1, 1, hd))
        cos, sin = L.rope_angles(jnp.array([[5]]), int(hd * rot_frac),
                                 10_000.0)
        out = L.apply_rope(x, cos, sin, rot_frac)
        np.testing.assert_allclose(np.asarray(out[..., 16:]), 1.0)


class TestFlashAttention:
    @pytest.mark.slow
    @given(st.integers(1, 3), st.integers(1, 4), st.integers(0, 100))
    @settings(max_examples=15, deadline=None)
    def test_chunked_equals_direct(self, b, g, seed):
        """Chunked flash == direct masked softmax attention for random
        GQA configurations."""
        cfg = dataclasses.replace(get_config("stablelm_1_6b").reduced(),
                                  n_heads=2 * g, n_kv_heads=2, head_dim=16)
        s = 128
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (b, s, 2 * g, 16))
        k = jax.random.normal(ks[1], (b, s, 2, 16))
        v = jax.random.normal(ks[2], (b, s, 2, 16))
        direct = L._direct_attention(q, k, v, cfg, causal=True, window=0,
                                     prefix_len=0)
        chunked = L.flash_attention(q, k, v, cfg, causal=True,
                                    q_chunk=32, kv_chunk=32)
        np.testing.assert_allclose(np.asarray(chunked), np.asarray(direct),
                                   rtol=2e-5, atol=2e-5)

    def test_prefix_lm_mask(self):
        """Prefix tokens attend bidirectionally; suffix is causal."""
        cfg = dataclasses.replace(get_config("paligemma_3b").reduced(),
                                  n_heads=2, n_kv_heads=1, head_dim=16)
        b, s, pre = 1, 64, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, s, 2, 16))
        k = jax.random.normal(ks[1], (b, s, 1, 16))
        v = jax.random.normal(ks[2], (b, s, 1, 16))
        out = L.flash_attention(q, k, v, cfg, causal=True, prefix_len=pre,
                                q_chunk=16, kv_chunk=16)
        # changing a FUTURE suffix token must not affect earlier suffix
        v2 = v.at[:, -1].add(10.0)
        out2 = L.flash_attention(q, k, v2, cfg, causal=True, prefix_len=pre,
                                 q_chunk=16, kv_chunk=16)
        np.testing.assert_allclose(np.asarray(out[:, :-1]),
                                   np.asarray(out2[:, :-1]), atol=1e-6)
        # but changing a PREFIX token affects position 0 (bidirectional)
        v3 = v.at[:, pre - 1].add(10.0)
        out3 = L.flash_attention(q, k, v3, cfg, causal=True, prefix_len=pre,
                                 q_chunk=16, kv_chunk=16)
        assert float(jnp.max(jnp.abs(out3[:, 0] - out[:, 0]))) > 1e-3


class TestMoE:
    @given(st.integers(0, 50))
    @settings(max_examples=15, deadline=None)
    def test_gates_normalized_and_capacity_respected(self, seed):
        """Softmax routing: the chosen gates sum to one, the load-balance
        loss is positive, and no expert has a capacity: every (token,
        choice) pair reaches its expert."""
        cfg = get_config("grok_1_314b").reduced()
        p, _ = MOE.init_moe(jax.random.PRNGKey(seed), cfg, jnp.float32)
        x = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(seed),
                                                 7), (2, 16, cfg.d_model))
        out, aux, stats = MOE.apply_moe(p, x, cfg)
        assert out.shape == x.shape
        assert bool(jnp.all(jnp.isfinite(out)))
        assert float(aux) > 0      # load-balance loss positive
        w, _, _ = MOE.route(p, x.reshape(-1, cfg.d_model), cfg)
        np.testing.assert_allclose(np.asarray(jnp.sum(w, -1)), 1.0,
                                   rtol=1e-6)
        assert int(stats["routed"]) == 32 * cfg.top_k == int(stats["rows"])

    def test_identical_tokens_identical_outputs(self):
        """Permutation-ish invariance: two identical tokens get identical
        expert outputs."""
        cfg = get_config("grok_1_314b").reduced()
        p, _ = MOE.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
        tok = jax.random.normal(jax.random.PRNGKey(1), (1, 1, cfg.d_model))
        x = jnp.tile(tok, (1, 4, 1))
        out, _, _ = MOE.apply_moe(p, x, cfg)
        np.testing.assert_allclose(np.asarray(out[0, 0]),
                                   np.asarray(out[0, 3]), rtol=1e-5,
                                   atol=1e-5)

    @pytest.mark.parametrize("arch", ["grok_1_314b", "moonshot_v1_16b_a3b"])
    def test_skewed_router_drops_no_token(self, arch):
        """A router that sends every token to the same top-k experts (the
        load a capacity would have cut at E/k of it): each token's output
        is still its own experts' weighted sum, as computed one token at a
        time."""
        cfg = get_config(arch).reduced()
        p, _ = MOE.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
        # every token carries feature 0 = 1, which the router reads as a
        # large score for experts 0..k-1
        x = x.at[..., 0].set(1.0)
        p = dict(p, router=p["router"].at[0, :cfg.top_k].add(100.0))
        out, _, stats = MOE.apply_moe(p, x, cfg)
        assert int(stats["max_load"]) == 64 == int(
            stats["routed"]) // cfg.top_k
        one = jnp.stack([MOE.apply_moe(p, x[b:b + 1, i:i + 1], cfg)[0][0, 0]
                         for b in range(2) for i in range(32)])
        # the same fp32 sums in another order (a grouped product over 64
        # rows against one row): 1e-4 of the output's scale
        np.testing.assert_allclose(np.asarray(out.reshape(64, -1)),
                                   np.asarray(one), rtol=0,
                                   atol=1e-4 * float(jnp.max(jnp.abs(one))))


class TestVocabPadding:
    def test_padded_logits_never_win_argmax(self):
        cfg = dataclasses.replace(get_config("seamless_m4t_medium").reduced(),
                                  vocab_size=500)   # pads to 512
        assert cfg.padded_vocab == 512
        params, _ = zoo.init_model(jax.random.PRNGKey(0), cfg)
        b = 2
        frames = jax.random.normal(jax.random.PRNGKey(1),
                                   (b, cfg.n_prefix_tokens, cfg.prefix_dim))
        toks = jax.random.randint(jax.random.PRNGKey(2), (b, 8), 0, 500)
        from repro.models import encdec as ED
        logits, _ = ED.encdec_forward(cfg, params, frames, toks, remat=False)
        assert logits.shape[-1] == 512
        assert int(jnp.max(jnp.argmax(logits, -1))) < 500
