"""Paper figure 1/2 analogue (claim C1): accuracy vs rounds AND vs simulated
wall-clock for every selection policy, paired topology/data across policies.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import numpy as np

from repro.configs import POLICIES, FLConfig, NOMAConfig, get_config
from repro.data import TaskConfig, bayes_optimal_accuracy
from repro.fl import compare_policies, time_to_accuracy


def run(*, smoke=False, out_path=None, seed=0, rounds=None, clients=24):
    import jax

    rounds = (10 if smoke else 40) if rounds is None else rounds
    cfg = dataclasses.replace(get_config("smollm_135m").reduced(),
                              d_model=64, d_ff=128, vocab_size=64)
    # alpha=0.1: near-single-topic clients — the paper's non-IID regime
    # where starving far clients (channel-greedy) actually loses topics
    fl = FLConfig(n_clients=clients, rounds=rounds, local_epochs=1,
                  local_batch=16, lr=0.4, samples_per_client=(48, 160),
                  dirichlet_alpha=0.1, seed=seed)
    ncfg = NOMAConfig()
    task = TaskConfig(vocab_size=64, n_topics=8, seq_len=33, seed=seed)
    policies = ("age_noma", "channel") if smoke else POLICIES

    t0 = time.time()
    hists = compare_policies(cfg, fl, ncfg, task, policies=policies,
                             rounds=rounds, seed=seed)
    wall = time.time() - t0
    bayes = bayes_optimal_accuracy(task)
    target = 0.3 * bayes

    rows = []
    for p, h in hists.items():
        tta = time_to_accuracy(h, target)
        rows.append({
            "policy": p,
            "final_acc": h.accuracy[-1],
            "final_loss": h.loss[-1],
            "sim_time_s": h.sim_time[-1],
            "mean_round_s": float(np.mean(h.round_time)),
            "max_age": int(max(h.max_age)),
            "clients_touched": int(np.count_nonzero(h.participation)),
            "time_to_half_bayes_s": tta,
        })

    result = {
        "benchmark": "fl_convergence",
        "backend": jax.default_backend(),
        "smoke": smoke,
        "rows": rows,
        "bayes_acc": bayes,
        "target_acc": target,
        "histories": {p: h.as_dict() for p, h in hists.items()},
        "wall_s": wall,
    }
    out_path = out_path or os.path.join("experiments", "bench",
                                        "BENCH_fl_convergence.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1, allow_nan=False)

    print("name,policy,final_acc,sim_time_s,max_age,tta_s")
    for r in rows:
        print(f"fl_convergence,{r['policy']},{r['final_acc']:.4f},"
              f"{r['sim_time_s']:.1f},{r['max_age']},"
              f"{r['time_to_half_bayes_s']}")
    print(f"wrote {out_path}")
    return result


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="fewer rounds + two policies for CI")
    ap.add_argument("--out", default=None, help="output JSON path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    run(smoke=args.smoke, out_path=args.out, seed=args.seed)


if __name__ == "__main__":
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                           / "src"))
    main()
