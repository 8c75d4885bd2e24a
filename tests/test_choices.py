"""The planner's choices (policy, pairing, selection, admission, engine,
predictor): ``FLConfig`` names and checks each, every per-call override
raises the same message through ``configs.base.check_choice``, ``FLServer``
plans under its config's policy, and ``engine.policy_priority`` is the one
map from a policy to its admission priority."""
import dataclasses
import re

import numpy as np
import pytest

from repro.configs import (
    AGE_POLICIES,
    POLICIES,
    FLConfig,
    NOMAConfig,
    get_config,
)
from repro.configs.base import CHOICES
from repro.core import pairing, plan
from repro.core.engine import WirelessEngine, policy_priority
from repro.core.scheduler import RoundEnv
from repro.data import TaskConfig
from repro.fl.predictor import UpdatePredictor
from repro.fl.rounds import run_montecarlo
from repro.fl.server import FLServer

TINY = dataclasses.replace(get_config("smollm_135m").reduced(),
                           d_model=32, d_ff=64, vocab_size=32, n_layers=2)
TASK = TaskConfig(vocab_size=32, n_topics=4, seq_len=17, seed=0)
FL = FLConfig(n_clients=8, rounds=3, local_epochs=1, local_batch=8,
              lr=0.2, samples_per_client=(24, 48), seed=0)
NCFG = NOMAConfig(n_subchannels=2)
BAD = "bogus"


def _env(n=8, seed=0):
    rng = np.random.default_rng(seed)
    return RoundEnv(gains=rng.exponential(size=n) * 1e-9,
                    n_samples=rng.uniform(200, 1200, size=n),
                    cpu_freq=rng.uniform(0.5e9, 2e9, size=n),
                    ages=rng.integers(1, 20, size=n).astype(np.float64),
                    model_bits=1e6)


def _mc_rounds(**kw):
    env = _env()
    eng = WirelessEngine(NCFG, FL)
    eng.montecarlo_rounds(env.gains[None, None], env.n_samples[None],
                          env.cpu_freq[None], 1e6, **kw)


def _flserver(**kw):
    FLServer(TINY, FL, NCFG, TASK, **kw)


def _engine(**kw):
    WirelessEngine(NCFG, FL, **kw)


def _schedule(**kw):
    WirelessEngine(NCFG, FL).schedule(_env(), **kw)


# (field, site): each builds or calls with ``field`` set to an unknown
# value, at construction or at the call, before any planning runs
SITES = {
    **{(f, "FLConfig"): (lambda f=f: FLConfig(**{f: BAD}))
       for f in ("policy", "pairing", "selection", "admission", "engine",
                 "predictor")},
    **{(f, "FLServer"): (lambda f=f: _flserver(**{f: BAD}))
       for f in ("policy", "pairing", "selection", "engine", "predictor")},
    **{(f, "WirelessEngine"): (lambda f=f: _engine(**{f: BAD}))
       for f in ("pairing", "selection", "admission")},
    ("pairing", "schedule"): lambda: _schedule(pairing=BAD),
    ("selection", "schedule"): lambda: _schedule(selection=BAD),
    ("policy", "montecarlo_rounds"): lambda: _mc_rounds(policy=BAD),
    ("pairing", "montecarlo_rounds"): lambda: _mc_rounds(pairing=BAD),
    ("selection", "montecarlo_rounds"): lambda: _mc_rounds(selection=BAD),
    ("admission", "montecarlo_rounds"): lambda: _mc_rounds(admission=BAD),
    ("policy", "run_montecarlo"): lambda: run_montecarlo(
        n_clients=8, n_seeds=2, rounds=1, policies=("age_noma", BAD)),
    ("admission", "run_montecarlo"): lambda: run_montecarlo(
        n_clients=8, n_seeds=2, rounds=1, admission=BAD),
    ("admission", "resolve_admission"): lambda: plan.resolve_admission(
        BAD, 8, 4),
    ("selection", "plan_round"): lambda: plan.plan_round(
        _env(), NCFG, FL, priority=np.ones(8), selection=BAD),
    ("pairing", "pair_candidates"): lambda: pairing.pair_candidates(
        _env().gains, np.arange(4), BAD),
    ("predictor", "UpdatePredictor"): lambda: UpdatePredictor(
        {}, FL, 8, mode=BAD),
}


@pytest.mark.parametrize("field,site", sorted(SITES),
                         ids=[f"{f}-{s}" for f, s in sorted(SITES)])
def test_unknown_choice_raises_one_message(field, site):
    msg = f"unknown {field} {BAD!r} (expected one of {CHOICES[field]})"
    with pytest.raises(ValueError, match=re.escape(msg)):
        SITES[field, site]()


def test_flserver_plans_under_flconfig_policy():
    """``FLConfig.policy`` alone picks the policy ``FLServer`` plans
    under: it selects what the ``policy=`` keyword selects."""
    by_cfg = FLServer(TINY, dataclasses.replace(FL, policy="channel"),
                      NCFG, TASK)
    by_kw = FLServer(TINY, FL, NCFG, TASK, policy="channel")
    assert by_cfg.policy == by_kw.policy == "channel"
    for _ in range(3):
        gains, n_samples, cpu_freq = by_cfg.scenario.step(by_cfg.rng)
        np.testing.assert_array_equal(by_kw.scenario.step(by_kw.rng)[0],
                                      gains)
        env = RoundEnv(gains=gains, n_samples=n_samples, cpu_freq=cpu_freq,
                       ages=by_cfg.ages, model_bits=by_cfg.model_bits)
        a, b = by_cfg.select(env), by_kw.select(env)
        np.testing.assert_array_equal(a.selected, b.selected)
        assert sorted(a.pairs) == sorted(b.pairs)
        assert a.t_round == b.t_round
        by_cfg.round_idx += 1
        by_kw.round_idx += 1


@pytest.mark.parametrize("policy", POLICIES)
def test_policy_priority(policy):
    n, slots, round_idx = 10, 4, 3
    gains = np.random.default_rng(0).exponential(size=(2, n))
    draws = []

    def draw():
        draws.append(policy)
        return np.full((2, n), 0.5)

    prio = policy_priority(policy, gains, round_idx, slots, draw)
    assert draws == ([policy] if policy == "random" else [])
    if policy in AGE_POLICIES:
        assert prio is None
    elif policy == "channel":
        assert prio is gains
    elif policy == "random":
        np.testing.assert_array_equal(prio, 0.5)
    else:
        # the numpy schedule_round_robin window, on every row
        window = {(round_idx * slots + i) % n for i in range(slots)}
        prio = np.asarray(prio)
        assert prio.shape == gains.shape
        for row in prio:
            assert set(np.argsort(-row, kind="stable")[:slots]) == window
