"""Experiment drivers: run one policy or compare all (the paper's figures),
plus the Monte-Carlo wireless driver (``run_montecarlo``) that sweeps every
selection/RA policy over S environment-realization seeds, the scenario
dynamics (repro.sim) stepping on device fused with the batched engine
(core/engine.py)."""
from __future__ import annotations

from typing import Optional

import numpy as np

from repro.configs.base import (  # noqa: F401  (POLICIES re-export)
    POLICIES, FLConfig, ModelConfig, NOMAConfig,
)
from repro.data import TaskConfig
from repro.fl.server import FLServer, History
from repro.obs import RunLedger, trace

# the Monte-Carlo driver covers every FLServer policy (engine-side
# round_robin/random priorities + budget auto-calibration); the old
# reduced tuple is kept as an alias for back-compat
MC_POLICIES = POLICIES


def run_experiment(model_cfg: ModelConfig, fl: FLConfig, nomacfg: NOMAConfig,
                   task: TaskConfig, policy: str, *, rounds=None,
                   verbose=False, seed=None, agg_impl=None,
                   predictor=None, pairing=None, selection=None) -> History:
    server = FLServer(model_cfg, fl, nomacfg, task, policy=policy,
                      seed=seed, agg_impl=agg_impl, predictor=predictor,
                      pairing=pairing, selection=selection)
    return server.run(rounds, verbose=verbose)


def compare_policies(model_cfg: ModelConfig, fl: FLConfig,
                     nomacfg: NOMAConfig, task: TaskConfig, *,
                     policies=POLICIES, rounds=None, verbose=False,
                     seed=None, predictor=None) -> dict[str, History]:
    """Same seed => identical client data/topology across policies; only the
    selection/RA differs (paired comparison, as the paper's figures do)."""
    return {p: run_experiment(model_cfg, fl, nomacfg, task, p, rounds=rounds,
                              verbose=verbose, seed=seed,
                              predictor=predictor)
            for p in policies}


def compare_predictors(model_cfg: ModelConfig, fl: FLConfig,
                       nomacfg: NOMAConfig, task: TaskConfig, *,
                       policy: str = "age_noma", modes=("none", "stale",
                                                        "ann"),
                       rounds=None, verbose=False, seed=None
                       ) -> dict[str, History]:
    """A/B the update predictor under ONE selection policy. Same seed =>
    identical topology, gains, selections, and local batches across modes
    (the predictor never touches the server rng), so differences are purely
    the blended predicted updates."""
    return {m: run_experiment(model_cfg, fl, nomacfg, task, policy,
                              rounds=rounds, verbose=verbose, seed=seed,
                              predictor=m)
            for m in modes}


def run_montecarlo(nomacfg: Optional[NOMAConfig] = None,
                   flcfg: Optional[FLConfig] = None, *,
                   n_clients: int = 64, n_seeds: int = 32, rounds: int = 20,
                   policies=MC_POLICIES, model_bits: float = 1e6,
                   t_budget: float = 0.0, seed: int = 0,
                   use_pallas: bool = False,
                   kernel_backend: Optional[str] = None,
                   scenario: str | object = "static_iid",
                   presampled: bool = False, shard: bool = False,
                   pairing: Optional[str] = None,
                   selection: Optional[str] = None,
                   admission: Optional[str] = None) -> dict:
    """Wireless-layer Monte-Carlo: compare selection/RA policies over
    ``n_seeds`` independent environment realizations x ``rounds``, one
    batched engine call per round.

    ``scenario`` (registry name, ``ScenarioConfig`` or ``Scenario``)
    selects the environment dynamics (``repro.sim``): the scenario state
    steps on device inside the rollout — one PRNG key threads through the
    fused loop and no host-side ``rounds x seeds x N`` gains array is ever
    materialized. ``presampled=True`` is the escape hatch that
    pre-generates the identical env sequence via ``Scenario.rollout`` and
    replays it through the pre-sampled engine path (bit-for-bit equal
    outputs; parity tests use it).

    Every policy sees the same scenario key, hence identical topologies,
    mobility, fading, CPU, and data-arrival traces (paired comparison).
    ``age_noma_budget`` auto-calibrates its budget to 2x the mean
    channel-greedy round time of round 0 when ``t_budget`` is unset,
    mirroring ``FLServer``. Returns per-policy raw per-round arrays plus a
    scalar ``summary`` (JSON-safe) with mean round time, total time,
    staleness, and the Jain fairness index of participation.

    With ``FLConfig.n_cells > 1`` the scenario's per-client cell
    association is threaded through to the cell-partitioned planner
    (each cell schedules its own K subchannels; global round time = max
    over cells) and ``handover_rate`` is the mean fraction of clients
    whose serving BS changed per round. Every summary carries the same
    key set regardless of policy or cell count — ``handover_rate`` /
    ``t_budget_s`` are None when inapplicable — so cross-policy and
    cross-config summary diffs never KeyError.

    The whole sweep is recorded to a JSONL run ledger under
    ``experiments/runs/`` (one ``policy_done`` event per policy with its
    summary; ``REPRO_LEDGER=0`` disables).
    """
    import jax
    import jax.numpy as jnp

    from repro.core.engine import WirelessEngine
    from repro.sim import as_scenario

    nomacfg = nomacfg or NOMAConfig()
    flcfg = flcfg or FLConfig()
    s, n, r = n_seeds, n_clients, rounds
    with trace.span("mc.call", seed=seed, drops=s * r):
        with trace.span("mc.setup", seed=seed):
            # subchannel pairing policy + admitted-set selection mode +
            # admission implementation: every POLICY x scenario sweep can
            # run any (pairing, selection, admission) combination
            # (core/pairing.py, core/plan.py; threaded through the fused MC
            # step — an unknown admission value raises in the engine
            # constructor, never a silent fallback)
            eng = WirelessEngine(nomacfg, flcfg,
                                 kernel_backend=kernel_backend,
                                 use_pallas=use_pallas, pairing=pairing,
                                 selection=selection, admission=admission)
            scn = as_scenario(scenario, nomacfg, flcfg)
            k_env = jax.random.PRNGKey(seed)

            multicell = flcfg.n_cells > 1
            envs = scn.rollout(k_env, r, (s, n)) if presampled else None
            auto_budget = None
            if "age_noma_budget" in policies and t_budget <= 0.0:
                # first_env deliberately replays round 0 of rollout's key
                # schedule so the budget calibration sees the same draws
                env0 = (tuple(a[0] for a in envs) if envs is not None
                        else scn.first_env(k_env, r, (s, n)))  # reprolint: disable=key-reuse
                ref = eng.schedule_batch(
                    env0[0], env0[1], env0[2], jnp.ones((s, n), jnp.float32),
                    model_bits, priority=env0[0],
                    cell=env0[3] if multicell else None)
                auto_budget = 2.0 * max(
                    float(np.asarray(ref.t_round).mean()), 1e-6)

            results: dict = {"summary": {}, "meta": {
                "n_clients": n, "n_seeds": s, "rounds": r,
                "model_bits": model_bits, "t_budget": t_budget,
                "scenario": scn.name, "presampled": bool(presampled),
                "slots": eng.prm.slots, "use_pallas": use_pallas,
                "kernel_backend": eng.kernel_backend,
                "kernel_impl": eng.impl,
                "pairing": eng.pairing, "selection": eng.selection,
                "admission": eng.admission,
                "n_cells": flcfg.n_cells, "cell_layout": flcfg.cell_layout}}
            ledger = RunLedger.open("montecarlo", {
                **results["meta"], "policies": list(policies), "seed": seed})
        try:
            for policy in policies:
                tb = t_budget
                if policy == "age_noma_budget" and tb <= 0.0:
                    tb = auto_budget
                if envs is not None:
                    out = eng.montecarlo_rounds(
                        np.asarray(envs.gains), np.asarray(envs.n_samples),
                        np.asarray(envs.cpu_freq), model_bits,
                        policy=policy, t_budget=tb, seed=seed, shard=shard,
                        cell_seq=np.asarray(envs.cell) if multicell
                        else None)
                else:
                    out = eng.montecarlo_scenario(
                        scn, rounds=r, n_seeds=s, n_clients=n,
                        model_bits=model_bits, policy=policy, t_budget=tb,
                        seed=seed, key=k_env, shard=shard)
                with trace.span("mc.collect", seed=seed) as sp:
                    t_round = np.asarray(out["t_round"])          # (R, S)
                    part = np.asarray(out["participation"])       # (S, N)
                    jain = (part.sum(1) ** 2                      # (S,)
                            / np.maximum(n * (part ** 2).sum(1), 1e-12))
                    results[policy] = {k: np.asarray(v)
                                       for k, v in out.items()}
                    sp.note(bytes=sum(v.nbytes for v in
                                      results[policy].values()))
                    # every policy emits the SAME summary key set (None when
                    # inapplicable) so cross-policy/config diffs never KeyError
                    results["summary"][policy] = {
                        "mean_t_round_s": float(t_round.mean()),
                        "total_time_s": float(t_round.sum(0).mean()),
                        "max_age": int(np.asarray(out["max_age"]).max()),
                        "mean_max_age": float(
                            np.asarray(out["max_age"]).mean()),
                        "jain_participation": float(jain.mean()),
                        # round-time decomposition of the bottleneck pair
                        # (means sum to mean_t_round_s within fp32 tolerance)
                        "mean_t_comp_bottleneck_s": float(
                            np.asarray(out["t_comp_bottleneck"]).mean()),
                        "mean_t_up_bottleneck_s": float(
                            np.asarray(out["t_up_bottleneck"]).mean()),
                        "mean_n_evicted": float(
                            np.asarray(out["n_evicted"]).mean()),
                        # population AoU histogram summed over rounds x seeds
                        # ((7,) counts on metrics.AOU_BUCKET_EDGES)
                        "aou_hist": np.asarray(out["aou_hist"])
                        .sum(axis=(0, 1)).tolist(),
                        "handover_rate": (
                            float(np.asarray(out["handovers"]).mean() / n)
                            if "handovers" in out else None),
                        "t_budget_s": (float(tb) if policy == "age_noma_budget"
                                       else None),
                    }
                    ledger.event("policy_done", policy=policy,
                                 summary=results["summary"][policy])
        finally:
            ledger.close()
    return results


def time_to_accuracy(hist: History, target: float) -> Optional[float]:
    """Simulated seconds to first reach ``target`` accuracy (None = never)."""
    for t, a in zip(hist.sim_time, hist.accuracy):
        if a >= target:
            return t
    return None
