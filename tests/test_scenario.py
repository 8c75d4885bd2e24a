"""Scenario subsystem tier (repro.sim): statistical pins for every
channel/mobility/compute/data process, the static_iid <-> legacy-stream
bitwise pin, and the fused-vs-presampled bit-for-bit Monte-Carlo parity
(DESIGN.md section 6).

Statistical tests use fixed seeds and wide sample sets so they are
deterministic; tolerances are quoted next to the estimator variance they
cover.
"""

import jax
import numpy as np
import pytest

from repro.configs import POLICIES, FLConfig, NOMAConfig
from repro.core import noma
from repro.fl.rounds import run_montecarlo
from repro.sim import (
    SCENARIOS,
    NumpyScenario,
    Scenario,
    ScenarioConfig,
    as_scenario,
    bessel_j0,
    get_scenario_config,
    jakes_rho,
)

NCFG = NOMAConfig(n_subchannels=3)
FLCFG = FLConfig()


def make(scfg: ScenarioConfig) -> Scenario:
    return Scenario(scfg, NCFG, FLCFG)


def roll_states(scn: Scenario, key, rounds, shape):
    """Step a scenario collecting (states, envs)."""
    state, keys = scn.init_and_keys(key, rounds, shape)
    states, envs = [], []
    for i in range(rounds):
        state, env = scn.step(state, keys[i])
        states.append(state)
        envs.append(env)
    return states, envs


# ---------------------------------------------------------------------------
# Jakes correlation / Bessel J0
# ---------------------------------------------------------------------------


class TestJakes:
    def test_bessel_j0_reference_values(self):
        # A&S table values (and the first zero of J0)
        assert bessel_j0(0.0) == pytest.approx(1.0, abs=1e-7)
        assert bessel_j0(1.0) == pytest.approx(0.7651976866, abs=1e-7)
        assert bessel_j0(2.404825557695773) == pytest.approx(0.0, abs=1e-6)
        assert bessel_j0(5.0) == pytest.approx(-0.1775967713, abs=1e-6)
        assert bessel_j0(10.0) == pytest.approx(-0.2459357645, abs=1e-6)

    def test_jakes_rho_limits(self):
        assert jakes_rho(0.0, 1e-3) == pytest.approx(1.0)
        # faster Doppler => less round-to-round correlation (before the
        # first J0 zero)
        rhos = [jakes_rho(f, 1e-3) for f in (5.0, 50.0, 200.0, 350.0)]
        assert all(a > b for a, b in zip(rhos, rhos[1:]))

    def test_registry_rhos(self):
        ped = make(SCENARIOS["pedestrian"])
        veh = make(SCENARIOS["vehicular"])
        assert ped.prm.rho_fading > 0.99
        assert veh.prm.rho_fading == pytest.approx(0.6425, abs=2e-3)


# ---------------------------------------------------------------------------
# channel processes
# ---------------------------------------------------------------------------


class TestChannelProcesses:
    def test_ar1_autocorrelation_matches_rho(self):
        """Lag-1 autocorrelation of the Gauss-Markov fading component must
        match the configured Jakes rho (tol covers the +-1/sqrt(chains*T)
        estimator noise at 4*64 chains x 300 steps)."""
        scfg = ScenarioConfig(name="t", channel="ar1", doppler_hz=200.0,
                              slot_s=1e-3)
        scn = make(scfg)
        states, _ = roll_states(scn, jax.random.PRNGKey(0), 300, (4, 64))
        x = np.stack([np.asarray(s.fading[..., 0]) for s in states])
        x0, x1 = x[:-1].ravel(), x[1:].ravel()
        rho_hat = np.sum(x0 * x1) / np.sum(x0 * x0)
        assert rho_hat == pytest.approx(scn.prm.rho_fading, abs=0.02)

    def test_ar1_stationary_power_is_exp1(self):
        """|h|^2 stays Exp(1) marginally: unit mean/variance."""
        scfg = ScenarioConfig(name="t", channel="ar1", doppler_hz=100.0,
                              slot_s=1e-3)
        states, _ = roll_states(make(scfg), jax.random.PRNGKey(1), 200,
                                (4, 64))
        p = np.stack([np.sum(np.asarray(s.fading) ** 2, -1)
                      for s in states[50:]]).ravel()
        assert p.mean() == pytest.approx(1.0, abs=0.05)
        assert p.var() == pytest.approx(1.0, abs=0.12)

    def test_iid_fading_is_exp1(self):
        """static_iid gains / path loss ~ Exp(1) — the exact
        noma.sample_gains distribution (KS distance over 64k samples)."""
        scn = make(SCENARIOS["static_iid"])
        states, envs = roll_states(scn, jax.random.PRNGKey(2), 50, (8, 128))
        d = np.asarray(states[0].pos)
        dist = np.maximum(np.linalg.norm(d, axis=-1), NCFG.min_radius_m)
        pl = NCFG.ref_path_loss * dist ** (-NCFG.path_loss_exp)
        fad = np.stack([np.asarray(e.gains) / pl for e in envs]).ravel()
        xs = np.sort(fad)
        ks = np.abs((np.arange(1, xs.size + 1) / xs.size)
                    - (1.0 - np.exp(-xs))).max()
        assert ks < 0.01

    def test_shadowing_variance_and_persistence(self):
        """Init shadowing is N(0, sigma^2) dB; static clients keep their
        draw (Gudmundson rho_s = 1 at v=0)."""
        scfg = ScenarioConfig(name="t", shadow_sigma_db=6.0)
        scn = make(scfg)
        states, _ = roll_states(scn, jax.random.PRNGKey(3), 5, (16, 128))
        sh0 = np.asarray(states[0].shadow_db)
        assert sh0.std() == pytest.approx(6.0, rel=0.05)
        np.testing.assert_array_equal(sh0, np.asarray(states[-1].shadow_db))

    def test_shadowing_decorrelates_with_speed(self):
        """Mobile clients shed their shadowing: autocorr ~ exp(-v T/d)."""
        scfg = ScenarioConfig(name="t", shadow_sigma_db=6.0,
                              shadow_decorr_m=20.0, mobility="waypoint",
                              speed_mps=(2.0, 2.0))
        scn = make(scfg)
        states, _ = roll_states(scn, jax.random.PRNGKey(4), 200, (4, 64))
        x = np.stack([np.asarray(s.shadow_db) for s in states[20:]])
        x0, x1 = x[:-1].ravel(), x[1:].ravel()
        rho_hat = np.sum(x0 * x1) / np.sum(x0 * x0)
        assert rho_hat == pytest.approx(np.exp(-2.0 / 20.0), abs=0.03)
        assert x.std() == pytest.approx(6.0, rel=0.1)


# ---------------------------------------------------------------------------
# mobility
# ---------------------------------------------------------------------------


class TestMobility:
    def test_waypoint_speed_bounds(self):
        v_lo, v_hi = 0.5, 1.5
        scfg = ScenarioConfig(name="t", mobility="waypoint",
                              speed_mps=(v_lo, v_hi), move_s=2.0)
        scn = make(scfg)
        states, _ = roll_states(scn, jax.random.PRNGKey(5), 60, (4, 32))
        pos = np.stack([np.asarray(s.pos) for s in states])
        step = np.linalg.norm(np.diff(pos, axis=0), axis=-1)
        assert step.max() <= v_hi * 2.0 + 1e-4
        speeds = np.stack([np.asarray(s.speed) for s in states])
        assert speeds.min() >= v_lo - 1e-6 and speeds.max() <= v_hi + 1e-6
        # waypoints live in the annulus, so positions stay in the cell
        r = np.linalg.norm(pos, axis=-1)
        assert r.max() <= NCFG.cell_radius_m + 1e-3

    def test_waypoint_actually_moves(self):
        scfg = ScenarioConfig(name="t", mobility="waypoint",
                              speed_mps=(1.0, 1.0), move_s=5.0)
        states, _ = roll_states(make(scfg), jax.random.PRNGKey(6), 20,
                                (2, 16))
        d0 = np.asarray(states[0].pos)
        d1 = np.asarray(states[-1].pos)
        assert np.linalg.norm(d1 - d0, axis=-1).mean() > 10.0

    def test_drift_reflects_at_cell_edge(self):
        scfg = ScenarioConfig(name="t", mobility="drift",
                              speed_mps=(20.0, 30.0), move_s=2.0)
        states, envs = roll_states(make(scfg), jax.random.PRNGKey(7), 100,
                                   (4, 32))
        r = np.stack([np.linalg.norm(np.asarray(s.pos), axis=-1)
                      for s in states])
        assert r.max() <= NCFG.cell_radius_m + 1e-3
        # distances fed to path loss respect the exclusion radius
        for e in envs[:5]:
            g = np.asarray(e.gains)
            assert np.isfinite(g).all() and (g > 0).all()

    def test_fixed_mobility_distances_constant(self):
        scn = make(SCENARIOS["static_iid"])
        states, _ = roll_states(scn, jax.random.PRNGKey(8), 10, (2, 16))
        np.testing.assert_array_equal(np.asarray(states[0].pos),
                                      np.asarray(states[-1].pos))


# ---------------------------------------------------------------------------
# compute + data heterogeneity
# ---------------------------------------------------------------------------


class TestHeterogeneity:
    def test_bursty_cpu_two_point_support_and_occupancy(self):
        p_t, p_r = 0.1, 0.3
        scfg = ScenarioConfig(name="t", compute="bursty",
                              throttle_factor=0.4, p_throttle=p_t,
                              p_recover=p_r)
        scn = make(scfg)
        states, envs = roll_states(scn, jax.random.PRNGKey(9), 400, (2, 64))
        base = np.asarray(states[0].cpu_base)
        for e in envs[:10]:
            cpu = np.asarray(e.cpu_freq)
            ratio = cpu / base.astype(np.float32)
            assert np.all(np.isclose(ratio, 1.0, rtol=1e-5)
                          | np.isclose(ratio, 0.4, rtol=1e-5))
        # two-state chain stationary occupancy p_t / (p_t + p_r)
        thr = np.stack([np.asarray(s.throttled) for s in states[100:]])
        assert thr.mean() == pytest.approx(p_t / (p_t + p_r), abs=0.04)

    def test_dynamic_data_bounded_and_varying(self):
        scfg = ScenarioConfig(name="t", data="dynamic", data_phi=0.85,
                              data_jitter=0.15)
        scn = make(scfg)
        states, envs = roll_states(scn, jax.random.PRNGKey(10), 100,
                                   (2, 64))
        base = np.asarray(states[0].n_base)
        ns = np.stack([np.asarray(e.n_samples) for e in envs])
        assert (ns >= np.maximum(0.2 * base, 1.0) - 1e-3).all()
        assert (ns <= 2.0 * base + 1e-3).all()
        assert ns.std(axis=0).min() > 0.0       # every client fluctuates

    def test_static_scenario_keeps_cpu_and_data(self):
        scn = make(SCENARIOS["static_iid"])
        _, envs = roll_states(scn, jax.random.PRNGKey(11), 5, (2, 16))
        np.testing.assert_array_equal(np.asarray(envs[0].cpu_freq),
                                      np.asarray(envs[-1].cpu_freq))
        np.testing.assert_array_equal(np.asarray(envs[0].n_samples),
                                      np.asarray(envs[-1].n_samples))


# ---------------------------------------------------------------------------
# numpy twin: legacy-stream + distribution pins
# ---------------------------------------------------------------------------


class TestNumpyTwin:
    def test_static_iid_is_the_legacy_stream_bitwise(self):
        """static_iid consumes exactly the legacy FLServer draws:
        (sample_distances, cpu uniform) at init, one Exp(1) gains vector
        per round — so enabling the scenario path changes nothing."""
        n = 24
        rng_s = np.random.default_rng(123)
        rng_l = np.random.default_rng(123)
        scn = NumpyScenario(get_scenario_config("static_iid"), NCFG, FLCFG)
        dist, cpu = scn.init(rng_s, n, n_samples=np.full(n, 500.0))
        dist_l = noma.sample_distances(rng_l, n, NCFG)
        cpu_l = rng_l.uniform(FLCFG.cpu_freq_range_ghz[0] * 1e9,
                              FLCFG.cpu_freq_range_ghz[1] * 1e9, n)
        np.testing.assert_array_equal(dist, dist_l)
        np.testing.assert_array_equal(cpu, cpu_l)
        for _ in range(4):
            g, ns, cf = scn.step(rng_s)
            np.testing.assert_array_equal(
                g, noma.sample_gains(rng_l, dist_l, NCFG))
            np.testing.assert_array_equal(ns, np.full(n, 500.0))
            np.testing.assert_array_equal(cf, cpu_l)

    def test_twin_matches_jax_statistics(self):
        """fp64 twin and f32 scenario agree on the log-gain distribution
        under a fully dynamic scenario (vehicular)."""
        scfg = SCENARIOS["vehicular"]
        rng = np.random.default_rng(0)
        tw = NumpyScenario(scfg, NCFG, FLCFG)
        tw.init(rng, 64)
        g_np = np.log10(np.stack([tw.step(rng)[0] for _ in range(150)]))
        _, envs = roll_states(make(scfg), jax.random.PRNGKey(12), 150,
                              (4, 64))
        g_jx = np.log10(np.stack([np.asarray(e.gains) for e in envs]))
        assert g_np.mean() == pytest.approx(g_jx.mean(), abs=0.15)
        assert g_np.std() == pytest.approx(g_jx.std(), rel=0.1)

    def test_twin_processes_cover_all_registered_scenarios(self):
        rng = np.random.default_rng(1)
        for name in SCENARIOS:
            tw = NumpyScenario(get_scenario_config(name), NCFG, FLCFG)
            tw.init(rng, 12)
            g, ns, cf = tw.step(rng)
            assert g.shape == ns.shape == cf.shape == (12,)
            assert np.isfinite(g).all() and (g > 0).all()


# ---------------------------------------------------------------------------
# fused Monte-Carlo parity + policy coverage
# ---------------------------------------------------------------------------


MC_KW = dict(n_clients=16, n_seeds=4, rounds=5, model_bits=4e6, seed=3)


class TestMonteCarloParity:
    @pytest.mark.slow
    @pytest.mark.parametrize("scenario", ["static_iid", "vehicular"])
    def test_fused_matches_presampled_bitwise(self, scenario):
        """The fused scenario loop and the ``presampled=`` escape hatch
        replay identical env sequences -> bit-identical outputs, for
        EVERY policy including the auto-calibrated budget one."""
        of = run_montecarlo(NCFG, FLCFG, policies=POLICIES,
                            scenario=scenario, **MC_KW)
        op = run_montecarlo(NCFG, FLCFG, policies=POLICIES,
                            scenario=scenario, presampled=True, **MC_KW)
        for p in POLICIES:
            for k in ("t_round", "n_selected", "max_age", "participation"):
                np.testing.assert_array_equal(of[p][k], op[p][k],
                                              err_msg=f"{p}/{k}")
        assert of["summary"]["age_noma_budget"]["t_budget_s"] == \
            op["summary"]["age_noma_budget"]["t_budget_s"]

    def test_rollout_deterministic_under_one_key(self):
        """Same key -> same env sequence: the pairing guarantee across
        policies in run_montecarlo."""
        scn = as_scenario("pedestrian", NCFG, FLCFG)
        a = scn.rollout(jax.random.PRNGKey(9), 4, (3, 8))
        b = scn.rollout(jax.random.PRNGKey(9), 4, (3, 8))
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    @pytest.mark.slow
    def test_every_registered_scenario_runs_fused(self):
        for name in SCENARIOS:
            out = run_montecarlo(NCFG, FLCFG, policies=("age_noma",),
                                 scenario=name, n_clients=12, n_seeds=2,
                                 rounds=3, model_bits=4e6, seed=0)
            assert out["meta"]["scenario"] == name
            t = out["age_noma"]["t_round"]
            assert t.shape == (3, 2) and np.isfinite(t).all()

    def test_engine_round_robin_matches_reference_window(self):
        """R non-overlapping windows cover each client exactly once:
        participation == 1 everywhere and Jain == 1 (the numpy
        schedule_round_robin semantics)."""
        ncfg = NOMAConfig(n_subchannels=2)       # slots 4
        out = run_montecarlo(ncfg, FLCFG, policies=("round_robin",),
                             n_clients=12, n_seeds=3, rounds=3,
                             model_bits=4e6, seed=0)
        part = out["round_robin"]["participation"]
        np.testing.assert_array_equal(part, np.ones_like(part))
        assert out["summary"]["round_robin"]["jain_participation"] == \
            pytest.approx(1.0)

    @pytest.mark.slow
    def test_engine_random_selects_slot_count(self):
        out = run_montecarlo(NCFG, FLCFG, policies=("random",),
                             n_clients=16, n_seeds=4, rounds=4,
                             model_bits=4e6, seed=0)
        np.testing.assert_array_equal(
            out["random"]["n_selected"],
            np.full((4, 4), NCFG.n_subchannels
                    * NCFG.users_per_subchannel))

    @pytest.mark.slow
    def test_budget_policy_respects_auto_budget(self):
        out = run_montecarlo(NCFG, FLCFG, policies=("age_noma_budget",),
                             **MC_KW)
        tb = out["summary"]["age_noma_budget"]["t_budget_s"]
        assert tb > 0
        assert out["age_noma_budget"]["t_round"].max() <= tb * (1 + 1e-5)


# ---------------------------------------------------------------------------
# registry / config plumbing
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_unknown_names_raise(self):
        with pytest.raises(ValueError, match="unknown scenario"):
            get_scenario_config("warp_drive")
        with pytest.raises(ValueError, match="channel"):
            make(ScenarioConfig(name="x", channel="quantum"))

    def test_as_scenario_accepts_all_spellings(self):
        s1 = as_scenario("vehicular", NCFG, FLCFG)
        s2 = as_scenario(SCENARIOS["vehicular"], NCFG, FLCFG)
        s3 = as_scenario(s1, NCFG, FLCFG)
        assert s1.prm == s2.prm and s3 is s1

    def test_params_are_hashable_static_args(self):
        prms = {make(c).prm for c in SCENARIOS.values()}
        assert len(prms) == len(SCENARIOS)
