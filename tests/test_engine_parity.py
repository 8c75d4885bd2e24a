"""Parity tier: the batched JAX engine (core/engine.py) against the numpy
scheduler (core/scheduler.py) as golden, over seeded RoundEnvs.

Covers both engine cores (the no-budget fast path and the lax.while_loop
eviction path), OMA mode, odd-candidate solo subchannels, eviction-
triggering budgets, and the Pallas rescoring mode (interpret on CPU).

Envs use continuous n_samples/gains so priorities are distinct almost
surely — exact key ties are resolved by different (but individually valid)
orders in the two implementations (DESIGN.md section 5.4).
"""
import dataclasses

import numpy as np
import pytest

from repro.configs import FLConfig, NOMAConfig
from repro.core import noma
from repro.core.engine import WirelessEngine
from repro.core.scheduler import RoundEnv, schedule_age_noma

FLCFG = FLConfig()
# few distinct (slots, n) shapes keep the jit cache small
CFG_SMALL = NOMAConfig(n_subchannels=3)    # slots 6
CFG_WIDE = NOMAConfig(n_subchannels=10)    # slots 20

RTOL = 1e-4   # fp32 engine vs fp64 reference
ATOL_P = 1e-5  # powers (issue acceptance)


def make_env(seed, n, ncfg, model_bits=4e6):
    rng = np.random.default_rng(seed)
    d = noma.sample_distances(rng, n, ncfg)
    return RoundEnv(
        gains=noma.sample_gains(rng, d, ncfg),
        n_samples=rng.uniform(100, 1000, n),
        cpu_freq=rng.uniform(0.5e9, 2e9, n),
        ages=rng.integers(1, 30, n),
        model_bits=model_bits)


def assert_parity(ref, out, *, check_pairs=True):
    np.testing.assert_array_equal(ref.selected, out.selected)
    if check_pairs:
        assert sorted(ref.pairs) == sorted(out.pairs)
    np.testing.assert_allclose(out.powers, ref.powers, atol=ATOL_P)
    np.testing.assert_allclose(out.rates, ref.rates, rtol=RTOL)
    np.testing.assert_allclose(out.t_com[ref.selected],
                               ref.t_com[ref.selected], rtol=RTOL)
    assert out.t_round == pytest.approx(ref.t_round, rel=RTOL)
    np.testing.assert_allclose(out.agg_weights, ref.agg_weights, rtol=RTOL)


class TestFastPathParity:
    """No budget -> the static-count scatter-free fast path."""

    @pytest.mark.parametrize("seed", range(10))
    @pytest.mark.parametrize("n,ncfg", [(16, CFG_SMALL), (40, CFG_WIDE)])
    def test_matches_numpy(self, seed, n, ncfg):
        env = make_env(seed, n, ncfg)
        eng = WirelessEngine(ncfg, FLCFG)
        ref = schedule_age_noma(env, ncfg, FLCFG)
        assert_parity(ref, eng.schedule(env))

    @pytest.mark.parametrize("seed", range(5))
    def test_oma_matches_numpy(self, seed):
        env = make_env(100 + seed, 16, CFG_SMALL)
        eng = WirelessEngine(CFG_SMALL, FLCFG)
        ref = schedule_age_noma(env, CFG_SMALL, FLCFG, oma=True)
        assert_parity(ref, eng.schedule(env, oma=True))

    @pytest.mark.parametrize("seed", range(5))
    def test_odd_candidates_solo_subchannel(self, seed):
        """n=5 < 6 slots: odd admission count, weakest client goes solo."""
        env = make_env(200 + seed, 5, CFG_SMALL)
        eng = WirelessEngine(CFG_SMALL, FLCFG)
        ref = schedule_age_noma(env, CFG_SMALL, FLCFG)
        out = eng.schedule(env)
        assert_parity(ref, out)
        solos = [p for p in out.pairs if p[1] == -1]
        assert len(solos) == 1

    def test_single_client(self):
        env = make_env(7, 1, CFG_SMALL)
        eng = WirelessEngine(CFG_SMALL, FLCFG)
        assert_parity(schedule_age_noma(env, CFG_SMALL, FLCFG),
                      eng.schedule(env))


class TestBudgetPathParity:
    """Positive budget -> the exact lax.while_loop eviction core."""

    @pytest.mark.parametrize("seed", range(8))
    def test_eviction_matches_numpy(self, seed):
        env = make_env(300 + seed, 16, CFG_SMALL, model_bits=2e7)
        eng = WirelessEngine(CFG_SMALL, FLCFG)
        free = schedule_age_noma(env, CFG_SMALL, FLCFG)
        budget = free.t_round * 0.5          # forces >= 1 eviction
        flb = dataclasses.replace(FLCFG, t_budget_s=budget)
        ref = schedule_age_noma(env, CFG_SMALL, flb)
        out = eng.schedule(env, t_budget=budget)
        assert ref.info["evicted"], "budget case must actually evict"
        assert sorted(ref.info["evicted"]) == sorted(out.info["evicted"])
        assert_parity(ref, out)

    @pytest.mark.parametrize("seed", range(4))
    def test_tiny_budget_evicts_to_floor(self, seed):
        """A budget below any feasible round time drains to <= 1 client
        exactly like the reference."""
        env = make_env(400 + seed, 12, CFG_SMALL, model_bits=2e7)
        eng = WirelessEngine(CFG_SMALL, FLCFG)
        budget = 1e-3
        flb = dataclasses.replace(FLCFG, t_budget_s=budget)
        ref = schedule_age_noma(env, CFG_SMALL, flb)
        out = eng.schedule(env, t_budget=budget)
        assert_parity(ref, out)

    def test_loose_budget_no_eviction(self):
        env = make_env(42, 16, CFG_SMALL)
        eng = WirelessEngine(CFG_SMALL, FLCFG)
        free = schedule_age_noma(env, CFG_SMALL, FLCFG)
        out = eng.schedule(env, t_budget=free.t_round * 10)
        assert_parity(free, out)
        assert out.info["evicted"] == []


class TestPallasParity:
    """kernel_backend="pallas" rescoring (interpret mode on CPU) must
    match too."""

    @pytest.mark.parametrize("seed", range(3))
    def test_pallas_rescore_matches_numpy(self, seed):
        env = make_env(500 + seed, 16, CFG_SMALL)
        eng = WirelessEngine(CFG_SMALL, FLCFG, kernel_backend="pallas")
        ref = schedule_age_noma(env, CFG_SMALL, FLCFG)
        assert_parity(ref, eng.schedule(env))


class TestPairingPolicyParity:
    """Every ``FLConfig.pairing`` policy agrees numpy<->jax on both engine
    cores (issue 4 acceptance)."""

    @pytest.mark.parametrize("pairing",
                             ["strong_weak", "adjacent", "hungarian",
                              "greedy_matching"])
    @pytest.mark.parametrize("seed", range(4))
    def test_fast_path_matches_numpy(self, pairing, seed):
        flp = dataclasses.replace(FLCFG, pairing=pairing)
        eng = WirelessEngine(CFG_SMALL, flp)
        for n in (5, 16):
            env = make_env(700 + seed, n, CFG_SMALL)
            ref = schedule_age_noma(env, CFG_SMALL, flp)
            assert_parity(ref, eng.schedule(env))

    @pytest.mark.parametrize("pairing",
                             ["adjacent", "hungarian", "greedy_matching"])
    @pytest.mark.parametrize("seed", range(3))
    def test_budget_path_matches_numpy(self, pairing, seed):
        flp = dataclasses.replace(FLCFG, pairing=pairing)
        eng = WirelessEngine(CFG_SMALL, flp)
        env = make_env(800 + seed, 16, CFG_SMALL, model_bits=2e7)
        budget = schedule_age_noma(env, CFG_SMALL, flp).t_round * 0.5
        flb = dataclasses.replace(flp, t_budget_s=budget)
        ref = schedule_age_noma(env, CFG_SMALL, flb)
        out = eng.schedule(env, t_budget=budget)
        assert sorted(ref.info["evicted"]) == sorted(out.info["evicted"])
        assert_parity(ref, out)

    @pytest.mark.parametrize("pairing", ["hungarian", "greedy_matching"])
    @pytest.mark.parametrize("seed", range(3))
    def test_oma_matches_numpy(self, pairing, seed):
        """OMA ablation: both sides score the completion table with OMA
        rates (partner-independent), so the guard keeps strong_weak
        deterministically on both."""
        flp = dataclasses.replace(FLCFG, pairing=pairing)
        eng = WirelessEngine(CFG_SMALL, flp)
        env = make_env(1000 + seed, 16, CFG_SMALL)
        ref = schedule_age_noma(env, CFG_SMALL, flp, oma=True)
        assert_parity(ref, eng.schedule(env, oma=True))

    @pytest.mark.slow
    @pytest.mark.parametrize("pairing", ["strong_weak", "hungarian"])
    def test_wide_slots_matches_numpy(self, pairing):
        """m > 3 exercises the assignment + multi-start 2-opt path."""
        flp = dataclasses.replace(FLCFG, pairing=pairing)
        eng = WirelessEngine(CFG_WIDE, flp)
        for seed in range(3):
            env = make_env(900 + seed, 40, CFG_WIDE)
            assert_parity(schedule_age_noma(env, CFG_WIDE, flp),
                          eng.schedule(env))

    def test_hungarian_never_slower_than_strong_weak_engine(self):
        eng_h = WirelessEngine(CFG_SMALL,
                               dataclasses.replace(FLCFG,
                                                   pairing="hungarian"))
        eng_sw = WirelessEngine(CFG_SMALL, FLCFG)
        for seed in range(8):
            env = make_env(950 + seed, 16, CFG_SMALL)
            assert eng_h.schedule(env).t_round <= \
                eng_sw.schedule(env).t_round * (1 + 1e-6)


class TestTiedSelectionParity:
    """The (priority, gain, index) lexicographic tiebreak: tied-age
    fixtures resolve by channel gain — identically in numpy and jax
    (the old epsilon-gain nudge was numerically vacuous and ties fell
    back to argsort order, systematically favouring low client indices)."""

    def _tied_env(self, seed, n, ages):
        rng = np.random.default_rng(seed)
        d = noma.sample_distances(rng, n, CFG_SMALL)
        return RoundEnv(
            gains=noma.sample_gains(rng, d, CFG_SMALL),
            n_samples=np.full(n, 500.0),     # equal weights => exact ties
            cpu_freq=rng.uniform(0.5e9, 2e9, n),
            ages=np.asarray(ages, np.int64),
            model_bits=4e6)

    @pytest.mark.parametrize("seed", range(5))
    def test_all_tied_selects_top_gains(self, seed):
        n = 20
        env = self._tied_env(seed, n, np.ones(n))
        ref = schedule_age_noma(env, CFG_SMALL, FLCFG)
        out = WirelessEngine(CFG_SMALL, FLCFG).schedule(env)
        top = set(np.argsort(-env.gains)[:6])
        assert set(np.flatnonzero(ref.selected)) == top
        np.testing.assert_array_equal(ref.selected, out.selected)
        assert sorted(ref.pairs) == sorted(out.pairs)

    @pytest.mark.parametrize("seed", range(5))
    def test_partial_ties_resolve_by_gain(self, seed):
        """Two age groups; within the boundary group the highest-gain
        clients win, not the lowest-index ones."""
        n = 20
        ages = np.ones(n)
        ages[:10] = 5                       # 10 tied candidates, 6 slots
        env = self._tied_env(100 + seed, n, ages)
        ref = schedule_age_noma(env, CFG_SMALL, FLCFG)
        out = WirelessEngine(CFG_SMALL, FLCFG).schedule(env)
        expect = set(np.arange(10)[np.argsort(-env.gains[:10])[:6]])
        assert set(np.flatnonzero(ref.selected)) == expect
        np.testing.assert_array_equal(ref.selected, out.selected)

    def test_tied_budget_path_parity(self):
        """The lexicographic order also drives the while-loop core's
        admission + backfill cursor."""
        n = 16
        env = self._tied_env(42, n, np.ones(n))
        env.model_bits = 2e7
        budget = schedule_age_noma(env, CFG_SMALL, FLCFG).t_round * 0.6
        flb = dataclasses.replace(FLCFG, t_budget_s=budget)
        ref = schedule_age_noma(env, CFG_SMALL, flb)
        out = WirelessEngine(CFG_SMALL, FLCFG).schedule(env,
                                                        t_budget=budget)
        np.testing.assert_array_equal(ref.selected, out.selected)
        assert sorted(ref.info["evicted"]) == sorted(out.info["evicted"])


class TestBatchedConsistency:
    def test_schedule_batch_matches_per_env(self):
        """One vmapped call == the same envs scheduled one by one."""
        import jax.numpy as jnp

        from repro.core.engine import engine_schedule_to_numpy

        envs = [make_env(600 + s, 16, CFG_SMALL) for s in range(6)]
        eng = WirelessEngine(CFG_SMALL, FLCFG)
        out = eng.schedule_batch(
            jnp.asarray(np.stack([e.gains for e in envs])),
            jnp.asarray(np.stack([e.n_samples for e in envs])),
            jnp.asarray(np.stack([e.cpu_freq for e in envs])),
            jnp.asarray(np.stack([e.ages for e in envs])),
            4e6)
        for b, env in enumerate(envs):
            single = eng.schedule(env)
            batched = engine_schedule_to_numpy(out, b)
            np.testing.assert_array_equal(single.selected, batched.selected)
            assert single.pairs == batched.pairs
            np.testing.assert_allclose(single.rates, batched.rates,
                                       rtol=1e-6)
            assert batched.t_round == pytest.approx(single.t_round,
                                                    rel=1e-6)

    def test_montecarlo_rollout_ages_consistent(self):
        """The MC driver's age dynamics match a manual per-round loop."""
        import jax

        eng = WirelessEngine(CFG_SMALL, FLCFG)
        S, N, R = 3, 12, 5
        rng = np.random.default_rng(0)
        key = jax.random.PRNGKey(0)
        dist = np.asarray(eng.sample_distances(key, (S, N)))
        gains = np.asarray(eng.sample_gains(
            jax.random.fold_in(key, 1),
            np.broadcast_to(dist, (R, S, N))))
        ns = rng.uniform(100, 1000, (S, N))
        cf = rng.uniform(0.5e9, 2e9, (S, N))
        out = eng.montecarlo_rounds(gains, ns, cf, 4e6)
        # replay seed 0 with the numpy scheduler
        ages = np.ones(N, dtype=np.int64)
        for r in range(R):
            env = RoundEnv(gains[r, 0], ns[0], cf[0], ages, 4e6)
            ref = schedule_age_noma(env, CFG_SMALL, FLCFG)
            ages = np.where(ref.selected, 1, ages + 1)
            assert out["t_round"][r, 0] == pytest.approx(ref.t_round,
                                                         rel=1e-4)
            assert int(out["max_age"][r, 0]) == int(ages.max())


class TestShardedSweep:
    """``shard=True`` on a 4-device CPU host (subprocess: the device-count
    flag must not leak into this process). The planner runs per device
    under ``shard_map`` (Mosaic kernels cannot be partitioned by XLA), so
    the sharded sweep and batch equal the one-device ones, the outputs
    span all four devices, and a count that does not divide over the
    devices raises instead of quietly running on one."""

    SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["REPRO_LEDGER"] = "0"
import sys
sys.path.insert(0, "src")
import jax
import numpy as np
from repro.configs import FLConfig, NOMAConfig
from repro.core.engine import WirelessEngine
from repro.fl.rounds import run_montecarlo
from repro.sim import as_scenario

assert len(jax.devices()) == 4
ncfg = NOMAConfig(n_subchannels=3)
kw = dict(n_clients=300, n_seeds=8, rounds=3, scenario="vehicular",
          policies=("age_noma", "random"), pairing="%s",
          kernel_backend="pallas_interpret")
one = run_montecarlo(ncfg, **kw)
four = run_montecarlo(ncfg, shard=True, **kw)
for pol in kw["policies"]:
    for k in ("participation", "final_ages", "n_selected", "max_age"):
        np.testing.assert_array_equal(four[pol][k], one[pol][k])
    np.testing.assert_allclose(four[pol]["t_round"], one[pol]["t_round"],
                               rtol=1e-6)

eng = WirelessEngine(ncfg, FLConfig(), kernel_backend="pallas_interpret",
                     pairing="%s")
scn = as_scenario("vehicular", ncfg, FLConfig())
out = eng.montecarlo_scenario(scn, rounds=2, n_seeds=8, n_clients=300,
                              model_bits=1e6, shard=True)
assert len(out["t_round"].sharding.device_set) == 4, out["t_round"].sharding

rng = np.random.default_rng(0)
env = (rng.exponential(1e-11, (8, 300)), rng.uniform(100, 1000, (8, 300)),
       rng.uniform(0.5e9, 2e9, (8, 300)), rng.integers(1, 30, (8, 300)))
b1 = eng.schedule_batch(*env, 4e6)
b4 = eng.schedule_batch(*env, 4e6, shard=True)
assert len(b4.t_round.sharding.device_set) == 4
np.testing.assert_array_equal(np.asarray(b4.selected), np.asarray(b1.selected))
np.testing.assert_allclose(np.asarray(b4.t_round), np.asarray(b1.t_round),
                           rtol=1e-6)

bad = (lambda: eng.montecarlo_scenario(scn, rounds=2, n_seeds=6,
                                       n_clients=300, model_bits=1e6,
                                       shard=True),
       lambda: eng.schedule_batch(*(a[:6] for a in env), 4e6, shard=True))
for call in bad:
    try:
        call()
    except ValueError as e:
        assert "does not divide over 4 devices" in str(e), e
    else:
        raise AssertionError("an indivisible shard=True did not raise")
print("OK")
"""

    @pytest.mark.parametrize("pairing", ["strong_weak", "hungarian"])
    def test_sharded_equals_one_device(self, pairing):
        import os
        import subprocess
        import sys
        out = subprocess.run(
            [sys.executable, "-c", self.SCRIPT % (pairing, pairing)],
            capture_output=True, text=True, cwd=os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))), timeout=540)
        assert out.returncode == 0, out.stderr[-3000:]
        assert "OK" in out.stdout
