"""Monte-Carlo policy sweep on the batched wireless engine.

Compares every selection/RA policy over S independent environment
realizations x R rounds, the scenario stepping fused into the batched
engine (``--scenario`` picks the dynamics from the repro.sim registry).
``--vs SCENARIO2`` additionally runs a paired second scenario (same seed,
same envs per policy) and prints how the age policy's fairness/staleness
advantage over channel-greedy moves between the two.

Measured effect (seed 0, 32 clients): temporally correlated fading over a
persistent topology (pedestrian / hotspot_shadowed) WIDENS the AoU
fairness advantage — greedy selection locks onto the same
favorably-shadowed clients for whole coherence windows (Jain gap 0.35 ->
~0.49) — while vehicular drift churns the gain ranking back toward
fairness (gap 0.23) at ~3x the age policy's round time. Writes raw arrays
to experiments/montecarlo_sweep.json.

    PYTHONPATH=src python examples/montecarlo_sweep.py \
        [--scenario static_iid] [--vs vehicular] [--seeds 32]
"""
import argparse
import json
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "src"))

import numpy as np  # noqa: E402


def print_table(name, out):
    print(f"--- scenario: {name} ---")
    print(f"{'policy':>16} {'mean T_round':>13} {'total time':>11} "
          f"{'mean max-age':>13} {'jain':>6}")
    for policy, s in out["summary"].items():
        print(f"{policy:>16} {s['mean_t_round_s']:>12.3f}s "
              f"{s['total_time_s']:>10.1f}s {s['mean_max_age']:>13.2f} "
              f"{s['jain_participation']:>6.3f}")


def advantage(out, metric, base="channel", ours="age_noma"):
    """age policy's edge over channel-greedy on a summary metric."""
    return out["summary"][base][metric] - out["summary"][ours][metric]


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--clients", type=int, default=64)
    ap.add_argument("--seeds", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--budget", type=float, default=0.0,
                    help="round-time budget in seconds (0 = none)")
    ap.add_argument("--scenario", default="static_iid",
                    help="repro.sim registry name")
    ap.add_argument("--vs", default=None, metavar="SCENARIO2",
                    help="paired second scenario (same seed) for an "
                         "age-advantage comparison, e.g. vehicular")
    ap.add_argument("--pairing", default="strong_weak",
                    help="subchannel pairing policy: strong_weak | "
                         "adjacent | hungarian | greedy_matching")
    ap.add_argument("--selection", default="greedy_set",
                    help="admitted-set selection mode: greedy_set | joint "
                         "(pairing-aware admission, core/plan.py)")
    args = ap.parse_args()

    from repro.configs import POLICIES, FLConfig, NOMAConfig
    from repro.fl.rounds import run_montecarlo

    def sweep(scenario):
        return run_montecarlo(
            NOMAConfig(n_subchannels=5), FLConfig(),
            n_clients=args.clients, n_seeds=args.seeds, rounds=args.rounds,
            policies=POLICIES, model_bits=1e6, t_budget=args.budget,
            seed=0, scenario=scenario, pairing=args.pairing,
            selection=args.selection)

    outs = {args.scenario: sweep(args.scenario)}
    if args.vs:
        outs[args.vs] = sweep(args.vs)
    for name, out in outs.items():
        print_table(name, out)

    if args.vs:
        a, b = args.scenario, args.vs

        def tail_age(out, policy):
            # p95 of the end-of-run per-client ages: the starved tail
            return float(np.percentile(out[policy]["final_ages"], 95))

        print(f"--- age_noma advantage over channel ({a} -> {b}) ---")
        print(f"{'staleness cut (mean max-age)':>30}: "
              f"{advantage(outs[a], 'mean_max_age'):8.2f} -> "
              f"{advantage(outs[b], 'mean_max_age'):8.2f}")
        print(f"{'starved-tail cut (p95 age)':>30}: "
              f"{tail_age(outs[a], 'channel') - tail_age(outs[a], 'age_noma'):8.2f} -> "
              f"{tail_age(outs[b], 'channel') - tail_age(outs[b], 'age_noma'):8.2f}")
        print(f"{'fairness gain (Jain)':>30}: "
              f"{-advantage(outs[a], 'jain_participation'):8.3f} -> "
              f"{-advantage(outs[b], 'jain_participation'):8.3f}")

    os.makedirs("experiments", exist_ok=True)
    path = "experiments/montecarlo_sweep.json"
    dump = {}
    for name, out in outs.items():
        d = {"meta": out["meta"], "summary": out["summary"]}
        for p in out["summary"]:
            d[p] = {k: np.asarray(v).tolist() for k, v in out[p].items()}
        dump[name] = d
    with open(path, "w") as f:
        json.dump(dump, f, allow_nan=False)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
