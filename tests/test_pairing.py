"""Pairing-policy subsystem tier (core/pairing.py, core/matching.py):
solver exactness against brute force, numpy<->jax solver agreement,
perfect-matching properties for every policy, and the hungarian policy's
optimality / never-slower guarantees (DESIGN.md section 7)."""
import itertools

import numpy as np
import pytest
from _hyp import given, settings, st

from repro.configs import PAIRINGS, FLConfig, NOMAConfig
from repro.core import aoi, matching, noma, pairing, roundtime
from repro.core.scheduler import (
    RoundEnv,
    exhaustive_pairing_reference,
    schedule_age_noma,
)

NCFG = NOMAConfig(n_subchannels=3)


def make_env(rng, n, model_bits=4e6):
    d = noma.sample_distances(rng, n, NCFG)
    return RoundEnv(
        gains=noma.sample_gains(rng, d, NCFG),
        n_samples=rng.integers(100, 1000, n).astype(float),
        cpu_freq=rng.uniform(0.5e9, 2e9, n),
        ages=aoi.init_ages(n),
        model_bits=model_bits)


def brute_force_min_sum(cost):
    m = cost.shape[0]
    return min(sum(cost[i, p[i]] for i in range(m))
               for p in itertools.permutations(range(m)))


class TestSolvers:
    """The assignment solvers against exhaustive permutation search."""

    @given(st.integers(1, 4), st.integers(0, 2 ** 32 - 1))
    @settings(max_examples=40, deadline=None)
    def test_hungarian_exact_vs_brute_force(self, m, seed):
        cost = np.random.default_rng(seed).uniform(0, 10, (m, m))
        sigma = pairing.hungarian_assignment(cost)
        assert sorted(sigma) == list(range(m))       # a permutation
        got = float(cost[np.arange(m), sigma].sum())
        assert got == pytest.approx(brute_force_min_sum(cost), abs=1e-9)

    @given(st.integers(1, 5), st.integers(0, 2 ** 32 - 1))
    @settings(max_examples=25, deadline=None)
    def test_jax_hungarian_matches_numpy(self, m, seed):
        cost = np.random.default_rng(seed).uniform(0, 10, (m, m))
        ref = pairing.hungarian_assignment(cost)
        jx = np.asarray(matching.hungarian_assignment(
            cost.astype(np.float32)))
        assert sorted(jx) == list(range(m))
        # both are min-sum optimal; with continuous costs the optimum is
        # unique a.s., so the assignments agree exactly
        np.testing.assert_array_equal(ref, jx)

    @given(st.integers(1, 5), st.integers(0, 2 ** 32 - 1))
    @settings(max_examples=25, deadline=None)
    def test_greedy_matches_numpy_and_is_matching(self, m, seed):
        score = np.random.default_rng(seed).uniform(0, 10, (m, m))
        ref = pairing.greedy_assignment(score)
        jx = np.asarray(matching.greedy_assignment(
            score.astype(np.float32)))
        assert sorted(ref) == list(range(m))
        np.testing.assert_array_equal(ref, jx)

    def test_batched_matches_single(self):
        rng = np.random.default_rng(0)
        cost = rng.uniform(0, 10, (8, 5, 5)).astype(np.float32)
        import jax.numpy as jnp
        out = np.asarray(matching.hungarian_assignment(jnp.asarray(cost)))
        for b in range(8):
            np.testing.assert_array_equal(
                out[b], pairing.hungarian_assignment(cost[b]))

    def test_padded_table_assigns_valid_to_valid(self):
        rng = np.random.default_rng(1)
        import jax.numpy as jnp
        cost = jnp.asarray(rng.uniform(0, 10, (7, 6, 6)), jnp.float32)
        m_valid = jnp.asarray([0, 1, 2, 3, 4, 5, 6])
        sig = np.asarray(matching.hungarian_assignment(
            matching.pad_cost_table(cost, m_valid)))
        for b, k in enumerate(np.asarray(m_valid)):
            assert sorted(sig[b][:k]) == list(range(k))


class TestPairCandidates:
    """Policy interface properties over random candidate sets."""

    @given(st.integers(2, 12), st.integers(0, 2 ** 32 - 1))
    @settings(max_examples=20, deadline=None)
    def test_perfect_matching(self, n_half, seed):
        """Every candidate appears in exactly one pair, strong has the
        higher gain — for every policy."""
        rng = np.random.default_rng(seed)
        n = 2 * n_half
        env = make_env(rng, n + 4)
        cand = rng.choice(n + 4, size=n, replace=False)
        t_cmp = roundtime.compute_times(env.n_samples, 2e6, env.cpu_freq, 1)
        for policy in PAIRINGS:
            pairs = pairing.pair_candidates(env.gains, cand, policy,
                                            t_cmp=t_cmp,
                                            model_bits=env.model_bits,
                                            ncfg=NCFG)
            members = [c for p in pairs for c in p]
            assert sorted(members) == sorted(cand)
            for s, w in pairs:
                assert env.gains[s] >= env.gains[w]

    @given(st.integers(0, 2 ** 32 - 1))
    @settings(max_examples=25, deadline=None)
    def test_hungarian_min_rate_not_worse_than_strong_weak(self, seed):
        """Bottleneck pair min-rate under hungarian >= strong_weak's."""
        rng = np.random.default_rng(seed)
        env = make_env(rng, 12)
        cand = np.arange(12)
        t_cmp = roundtime.compute_times(env.n_samples, 2e6, env.cpu_freq, 1)

        def bottleneck(policy):
            pairs = pairing.pair_candidates(
                env.gains, cand, policy, t_cmp=t_cmp,
                model_bits=env.model_bits, ncfg=NCFG)
            return min(float(noma.pair_min_rate(
                env.gains[s:s + 1], env.gains[w:w + 1], NCFG)[0])
                for s, w in pairs)

        assert bottleneck("hungarian") >= \
            bottleneck("strong_weak") * (1 - 1e-12)

    @given(st.integers(0, 2 ** 32 - 1))
    @settings(max_examples=25, deadline=None)
    def test_hungarian_never_slower_than_strong_weak(self, seed):
        rng = np.random.default_rng(seed)
        env = make_env(rng, 16)
        t_h = schedule_age_noma(env, NCFG,
                                FLConfig(pairing="hungarian")).t_round
        t_sw = schedule_age_noma(env, NCFG, FLConfig()).t_round
        assert t_h <= t_sw + 1e-12

    @given(st.integers(0, 2 ** 32 - 1))
    @settings(max_examples=20, deadline=None)
    def test_hungarian_matches_exhaustive_small(self, seed):
        """|cand| <= 8: the hungarian policy (exact bottleneck enumeration
        at m <= 4) reproduces the exhaustive optimal round time."""
        rng = np.random.default_rng(seed)
        for n, k in ((6, 3), (8, 4)):
            ncfg = NOMAConfig(n_subchannels=k)
            env = make_env(rng, n)
            s = schedule_age_noma(env, ncfg, FLConfig(pairing="hungarian"))
            opt = exhaustive_pairing_reference(list(range(n)), env, ncfg,
                                               FLConfig())
            assert s.t_round <= opt * 1.01 + 1e-9

    def test_adjacent_pairs_neighbours(self):
        rng = np.random.default_rng(3)
        env = make_env(rng, 8)
        pairs = pairing.pair_candidates(env.gains, np.arange(8), "adjacent",
                                        ncfg=NCFG)
        order = np.argsort(-env.gains)
        expect = [(int(order[2 * i]), int(order[2 * i + 1]))
                  for i in range(4)]
        assert pairs == expect

    def test_unknown_policy_raises(self):
        rng = np.random.default_rng(0)
        env = make_env(rng, 4)
        with pytest.raises(ValueError):
            pairing.pair_candidates(env.gains, np.arange(4), "nope",
                                    ncfg=NCFG)
        from repro.core.engine import WirelessEngine
        with pytest.raises(ValueError):
            WirelessEngine(NCFG, FLConfig(pairing="nope"))


class TestTwoOpt:
    @given(st.integers(2, 6), st.integers(0, 2 ** 32 - 1))
    @settings(max_examples=20, deadline=None)
    def test_refine_never_worse_and_stays_matching(self, m, seed):
        rng = np.random.default_rng(seed)
        table = rng.uniform(0, 10, (2 * m, 2 * m))
        table = np.maximum(table, table.T)      # symmetric-ish completion
        a0 = np.arange(m)
        b0 = np.arange(2 * m - 1, m - 1, -1)
        a, b = pairing.two_opt_refine(table, a0, b0)
        assert sorted(np.concatenate([a, b])) == list(range(2 * m))
        assert np.all(a < b)
        assert table[a, b].max() <= table[a0, b0].max() + 1e-12

    @pytest.mark.slow
    @given(st.integers(2, 5), st.integers(0, 2 ** 32 - 1))
    @settings(max_examples=15, deadline=None)
    def test_jax_refine_matches_numpy(self, m, seed):
        import jax.numpy as jnp
        rng = np.random.default_rng(seed)
        table = rng.uniform(0, 10, (2 * m, 2 * m)).astype(np.float32)
        a0 = np.arange(m)
        b0 = m + pairing.hungarian_assignment(table[:m, m:])
        ra, rb = pairing.two_opt_refine(table, a0, b0)
        ja, jb = matching.two_opt_refine(jnp.asarray(table),
                                         jnp.asarray(a0, jnp.int32),
                                         jnp.asarray(b0, jnp.int32))
        np.testing.assert_array_equal(ra, np.asarray(ja))
        np.testing.assert_array_equal(rb, np.asarray(jb))


class TestMonteCarloPairing:
    @pytest.mark.slow
    def test_run_montecarlo_accepts_pairing(self):
        """Every pairing policy threads through the fused MC sweep; the
        age-NOMA hungarian sweep is never slower per round than
        strong_weak on the same environments."""
        from repro.fl.rounds import run_montecarlo
        outs = {}
        for p in PAIRINGS:
            outs[p] = run_montecarlo(
                n_clients=12, n_seeds=4, rounds=4,
                policies=("age_noma",), pairing=p, seed=0)
            assert outs[p]["meta"]["pairing"] == p
        t = {p: np.asarray(o["age_noma"]["t_round"])
             for p, o in outs.items()}
        assert np.all(t["hungarian"] <= t["strong_weak"] * (1 + 1e-5))
        # adjacent is the NOMA worst case: not faster than strong_weak
        assert t["adjacent"].mean() >= t["strong_weak"].mean() * (1 - 1e-6)

    @pytest.mark.slow
    def test_budget_policy_runs_all_pairings(self):
        from repro.fl.rounds import run_montecarlo
        for p in ("hungarian", "greedy_matching"):
            out = run_montecarlo(n_clients=10, n_seeds=2, rounds=3,
                                 policies=("age_noma_budget",), pairing=p,
                                 seed=1)
            assert np.all(np.asarray(
                out["age_noma_budget"]["n_selected"]) >= 1)
