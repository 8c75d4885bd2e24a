"""Per-kernel correctness: Pallas (interpret mode) vs pure-jnp oracle,
sweeping shapes/dtypes (hypothesis for the shape sweeps)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels import ops, ref


class TestFedAgg:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("c,n", [(1, 512), (4, 2048), (10, 70_000)])
    def test_matches_oracle(self, dtype, c, n):
        k = jax.random.PRNGKey(0)
        u = jax.random.normal(k, (c, n), dtype)
        w = jax.random.uniform(jax.random.PRNGKey(1), (c,))
        out_ref = ref.weighted_sum_ref(u, w)
        out_pal = ops.weighted_sum(u, w, impl="interpret")
        tol = 1e-6 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(out_pal), np.asarray(out_ref),
                                   rtol=tol, atol=tol)

    @pytest.mark.parametrize("c,n", [(50, 3000), (200, 1000)])
    def test_c_tiles_within_fp32_summation_bound(self, c, n):
        """Cohorts past one C tile (200 = 128 + a ragged 72) accumulate
        across grid steps; each output stays within the fp32 summation
        bound c * eps * sum|w u| of the fp64 weighted sum."""
        u = jax.random.normal(jax.random.PRNGKey(c), (c, n), jnp.float32)
        w = jax.random.uniform(jax.random.PRNGKey(c + 1), (c,))
        out = np.asarray(ops.weighted_sum(u, w, impl="interpret"),
                         np.float64)
        u64, w64 = np.asarray(u, np.float64), np.asarray(w, np.float64)
        bound = c * np.finfo(np.float32).eps * (np.abs(w64) @ np.abs(u64))
        assert np.all(np.abs(out - w64 @ u64) <= bound)

    @pytest.mark.slow
    @given(st.integers(1, 12), st.integers(1, 5000),
           st.integers(0, 2 ** 31 - 1))
    @settings(max_examples=20, deadline=None)
    def test_shape_sweep(self, c, n, seed):
        k = jax.random.PRNGKey(seed)
        u = jax.random.normal(k, (c, n), jnp.float32)
        w = jax.random.uniform(jax.random.fold_in(k, 1), (c,))
        out_ref = ref.weighted_sum_ref(u, w)
        out_pal = ops.weighted_sum(u, w, impl="interpret", block_n=512)
        np.testing.assert_allclose(np.asarray(out_pal), np.asarray(out_ref),
                                   rtol=1e-5, atol=1e-5)

    def test_multi_dim_updates(self):
        """Pytree-leaf shapes (matrices) aggregate correctly."""
        k = jax.random.PRNGKey(2)
        u = jax.random.normal(k, (3, 17, 33), jnp.float32)
        w = jnp.array([0.2, 0.3, 0.5])
        out = ops.weighted_sum(u, w, impl="interpret")
        expect = jnp.einsum("cij,c->ij", u, w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-5, atol=1e-5)


class TestPairScore:
    KW = dict(n0b=1e-14, pmax=0.2, bw=1e6)

    @pytest.mark.slow
    @given(st.integers(1, 300), st.integers(0, 2 ** 31 - 1))
    @settings(max_examples=10, deadline=None)
    def test_kernel_matches_xla_twin(self, m, seed):
        """Fused Pallas pair-rate scoring == the jnp twin on any shape
        (tiles are (8, 128)-padded internally)."""
        rng = np.random.default_rng(seed)
        g_i = rng.uniform(1e-16, 1e-9, m).astype(np.float32)
        g_j = np.minimum(g_i, rng.uniform(1e-16, 1e-9, m)).astype(np.float32)
        from repro.kernels import pairscore
        ref = pairscore.pair_alloc_rates(g_i, g_j, impl="xla", **self.KW)
        pal = pairscore.pair_alloc_rates(g_i, g_j, impl="interpret",
                                         **self.KW)
        for r, p in zip(ref, pal):
            np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                                       rtol=1e-6, atol=1e-9)

    def test_oma_mode_and_matrix(self):
        from repro.kernels import pairscore
        rng = np.random.default_rng(0)
        g_i = rng.uniform(1e-14, 1e-10, 17).astype(np.float32)
        g_j = g_i * 0.3
        ref = pairscore.pair_alloc_rates(g_i, g_j, oma=True, impl="xla",
                                         **self.KW)
        pal = pairscore.pair_alloc_rates(g_i, g_j, oma=True,
                                         impl="interpret", **self.KW)
        for r, p in zip(ref, pal):
            np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                                       rtol=1e-6)
        score = pairscore.pair_score_matrix(g_i[:5], g_j, **self.KW)
        assert score.shape == (5, 17)
        assert np.all(np.asarray(score) > 0)

    def test_completion_table_matches_numpy_twin(self):
        """kernels completion_table == pairing.completion_table (fp64
        planner reference) within fp32 tol — the round planner's shared
        matching/search surface (DESIGN.md 8.3); exercised through the
        ops dispatch facade."""
        from repro.configs import NOMAConfig
        from repro.core import pairing
        from repro.kernels import ops
        cfg = NOMAConfig()
        rng = np.random.default_rng(5)
        g = np.sort(rng.uniform(1e-14, 1e-10, 8))[::-1].copy()
        tc = rng.uniform(0.1, 2.0, 8)
        mb = 4e6
        ref = pairing.completion_table(g, g, tc, tc, mb, cfg)
        out = ops.completion_table(
            g.astype(np.float32), tc.astype(np.float32), mb,
            n0b=cfg.noise_density * cfg.bandwidth_hz, pmax=cfg.max_power_w,
            bw=cfg.bandwidth_hz)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4)

    def test_matches_numpy_reference_formulas(self):
        """Kernel math == core.noma closed forms (fp64) within fp32 tol."""
        from repro.configs import NOMAConfig
        from repro.core import noma
        from repro.kernels import pairscore
        cfg = NOMAConfig()
        rng = np.random.default_rng(3)
        g_j = rng.uniform(1e-16, 1e-10, 64)
        g_i = g_j * rng.uniform(1.0, 100.0, 64)
        p_i, p_j = noma.pair_power_allocation(g_i, g_j, cfg)
        r_i, r_j = noma.pair_rates(p_i, p_j, g_i, g_j, cfg)
        ki, kj, kri, krj = pairscore.pair_alloc_rates(
            g_i.astype(np.float32), g_j.astype(np.float32),
            n0b=cfg.noise_density * cfg.bandwidth_hz,
            pmax=cfg.max_power_w, bw=cfg.bandwidth_hz, impl="xla")
        np.testing.assert_allclose(np.asarray(kj), p_j, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(kri), r_i, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(krj), r_j, rtol=1e-5)


class TestPlannerTables:
    """Fused planner kernel (kernels/planner.py) vs its XLA twin and the
    fp64 pairing reference — the mixed-precision contract of DESIGN.md
    section 13: bf16 table tiles (rtol ~1e-2), fp32 reductions
    (row_min/t_sw, rtol ~1e-6)."""

    KW = dict(n0b=1e-14, pmax=0.2, bw=1e6)

    def _cands(self, seed, b, c):
        rng = np.random.default_rng(seed)
        g = np.sort(rng.uniform(1e-14, 1e-10, (b, c)), axis=-1)[:, ::-1]
        tc = rng.uniform(0.05, 0.5, (b, c))
        return g.astype(np.float32).copy(), tc.astype(np.float32)

    @pytest.mark.parametrize("oma", [False, True])
    @pytest.mark.parametrize("c", [1, 2, 3, 7, 10, 129, 256])
    def test_fused_matches_xla_twin_tile_boundaries(self, c, oma):
        """Tile-boundary shapes: none of these c are multiples of the
        (8, 128) tile, so padding rows/columns must be masked out of
        every reduction. c=1 has no pairs (t_sw = 0), c=2 is the
        single-pair row."""
        from repro.kernels import planner
        g, tc = self._cands(11 * c + oma, 2, c)
        ref_t, ref_rm, ref_sw = planner.planner_tables(
            g, tc, 4e6, impl="xla", oma=oma, **self.KW)
        pal_t, pal_rm, pal_sw = planner.planner_tables(
            g, tc, 4e6, impl="interpret", oma=oma, **self.KW)
        assert pal_t.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(pal_t, np.float32), np.asarray(ref_t), rtol=1e-2)
        np.testing.assert_allclose(np.asarray(pal_rm), np.asarray(ref_rm),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(pal_sw), np.asarray(ref_sw),
                                   rtol=1e-6)

    def test_single_pair_semantics(self):
        """c=2: t_sw is exactly the one off-diagonal pair entry and
        row_min the off-diagonal minimum (fp32, pre-bf16 values)."""
        from repro.kernels import planner
        g, tc = self._cands(7, 1, 2)
        _, rm, sw = planner.planner_tables(g, tc, 4e6, impl="interpret",
                                           **self.KW)
        ref_t, _, _ = planner.planner_tables(g, tc, 4e6, impl="xla",
                                             **self.KW)
        assert float(sw[0]) == pytest.approx(float(ref_t[0, 0, 1]),
                                             rel=1e-6)
        assert float(rm[0, 0]) == pytest.approx(float(ref_t[0, 0, 1]),
                                                rel=1e-6)
        assert float(rm[0, 1]) == pytest.approx(float(ref_t[0, 1, 0]),
                                                rel=1e-6)

    def test_ops_facade_completion_table_routes_to_fused(self):
        """ops.completion_table(impl='interpret') returns the fused
        kernel's bf16 tiles upcast to fp32, matching xla at bf16 tol."""
        g, tc = self._cands(3, 4, 10)
        ref = ops.completion_table(g, tc, 4e6, impl="xla", **self.KW)
        out = ops.completion_table(g, tc, 4e6, impl="interpret", **self.KW)
        assert out.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-2)

    @pytest.mark.slow
    @given(st.integers(2, 40), st.integers(0, 2 ** 31 - 1))
    @settings(max_examples=10, deadline=None)
    def test_bf16_table_vs_fp64_reference(self, c, seed):
        """Property: the bf16 table tracks the fp64 numpy planner
        reference (core/pairing.py) within the documented tier —
        bf16's ~3 decimal digits on top of the fp32-vs-fp64 gap."""
        from repro.configs import NOMAConfig
        from repro.core import pairing
        from repro.kernels import planner
        cfg = NOMAConfig()
        rng = np.random.default_rng(seed)
        g64 = np.sort(rng.uniform(1e-14, 1e-10, c))[::-1].copy()
        tc64 = rng.uniform(0.05, 0.5, c)
        ref = pairing.completion_table(g64, g64, tc64, tc64, 4e6, cfg)
        tab, rm, _ = planner.planner_tables(
            g64.astype(np.float32), tc64.astype(np.float32), 4e6,
            impl="interpret", n0b=cfg.noise_density * cfg.bandwidth_hz,
            pmax=cfg.max_power_w, bw=cfg.bandwidth_hz)
        np.testing.assert_allclose(np.asarray(tab, np.float32), ref,
                                   rtol=2e-2)
        # row_min never saw bf16: fp32-vs-fp64 tolerance only
        off = np.where(np.eye(c, dtype=bool), np.inf, ref)
        np.testing.assert_allclose(np.asarray(rm), off.min(axis=1),
                                   rtol=1e-4)


class TestWKV6:
    @pytest.mark.parametrize("t,chunk", [(32, 16), (64, 64), (96, 32)])
    @pytest.mark.parametrize("c", [8, 16])
    def test_matches_recurrence(self, t, chunk, c):
        b, h = 2, 3
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        r, k, v = (jax.random.normal(ks[i], (b, h, t, c)) * 0.5
                   for i in range(3))
        wl = -jnp.exp(jax.random.normal(ks[3], (b, h, t, c)))
        u = jax.random.normal(ks[4], (h, c)) * 0.5
        out_ref, _ = ref.wkv6_ref(r, k, v, wl, u, jnp.zeros((b, h, c, c)))
        out_pal, _ = ops.wkv6(r, k, v, wl, u, impl="interpret", chunk=chunk)
        np.testing.assert_allclose(np.asarray(out_pal), np.asarray(out_ref),
                                   rtol=2e-4, atol=2e-4)

    def test_strong_decay_no_underflow(self):
        """Near-zero decays (w_log << 0) stay finite in the chunked form."""
        b, h, t, c = 1, 1, 128, 16
        ks = jax.random.split(jax.random.PRNGKey(1), 4)
        r, k, v = (jax.random.normal(ks[i], (b, h, t, c)) for i in range(3))
        wl = jnp.full((b, h, t, c), -20.0)    # decay ~ e^-20 per step
        u = jnp.zeros((h, c))
        out, _ = ops.wkv6(r, k, v, wl, u, impl="interpret", chunk=64)
        assert bool(jnp.all(jnp.isfinite(out)))

    def test_model_chunked_path_matches(self):
        """The model-side wkv6_chunked (used by rwkv blocks) == oracle."""
        from repro.models.rwkv import wkv6_chunked
        b, h, t, c = 2, 2, 96, 16
        ks = jax.random.split(jax.random.PRNGKey(2), 5)
        r, k, v = (jax.random.normal(ks[i], (b, h, t, c)) * 0.5
                   for i in range(3))
        wl = -jnp.exp(jax.random.normal(ks[3], (b, h, t, c)))
        u = jax.random.normal(ks[4], (h, c)) * 0.5
        s0 = jax.random.normal(ks[0], (b, h, c, c)) * 0.1
        o1, s1 = ref.wkv6_ref(r, k, v, wl, u, s0)
        o2, s2 = wkv6_chunked(r, k, v, wl, u, s0, chunk=32)
        np.testing.assert_allclose(np.asarray(o2), np.asarray(o1),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(s2), np.asarray(s1),
                                   rtol=2e-4, atol=2e-4)


class TestSWA:
    @pytest.mark.parametrize("s,window,bq,bk", [
        (256, 128, 128, 128), (512, 256, 128, 128), (512, 128, 256, 128)])
    def test_matches_oracle(self, s, window, bq, bk):
        b, h, kh, hd = 1, 4, 2, 32
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, kh, hd), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, kh, hd), jnp.float32)
        o_ref = ref.swa_ref(q, k, v, window)
        o_pal = ops.swa(q, k, v, window=window, impl="interpret", bq=bq,
                        bk=bk)
        np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                                   rtol=2e-5, atol=2e-5)

    def test_window_actually_limits(self):
        """Tokens beyond the window must NOT influence the output."""
        b, s, h, kh, hd, w = 1, 256, 2, 1, 16, 64
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (b, s, h, hd))
        k = jax.random.normal(ks[1], (b, s, kh, hd))
        v = jax.random.normal(ks[2], (b, s, kh, hd))
        out1 = ops.swa(q, k, v, window=w, impl="interpret", bq=64, bk=64)
        # perturb tokens far outside the window of the last query
        k2 = k.at[:, :64].set(jax.random.normal(ks[0], (b, 64, kh, hd)))
        v2 = v.at[:, :64].set(0.0)
        out2 = ops.swa(q, k2, v2, window=w, impl="interpret", bq=64, bk=64)
        np.testing.assert_allclose(np.asarray(out1[:, -1]),
                                   np.asarray(out2[:, -1]), rtol=1e-6)

    def test_matches_flash_attention_path(self):
        """Model flash_attention(window=...) == swa oracle (same math)."""
        from repro.configs import get_config
        from repro.models.layers import flash_attention
        import dataclasses
        cfg = dataclasses.replace(get_config("stablelm_1_6b").reduced(),
                                  n_heads=4, n_kv_heads=2, head_dim=16)
        b, s, w = 1, 512, 128
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (b, s, 4, 16))
        k = jax.random.normal(ks[1], (b, s, 2, 16))
        v = jax.random.normal(ks[2], (b, s, 2, 16))
        o_model = flash_attention(q, k, v, cfg, causal=True, window=w,
                                  q_chunk=128, kv_chunk=128)
        o_ref = ref.swa_ref(q, k, v, w)
        np.testing.assert_allclose(np.asarray(o_model), np.asarray(o_ref),
                                   rtol=2e-5, atol=2e-5)
