"""Joint (pairing-aware) selection vs the greedy-sequential pipeline vs the
exhaustive joint (set x matching) optimum.

Per instance size (4/6/8 clients — the exhaustive joint reference's range,
plus a larger no-reference size for the swap/prune branch) this measures
the scheduled round time of ``FLConfig.selection = greedy_set | joint``
against (a) the exhaustive optimum over ALL candidate sets x ALL pairings
(``plan.exhaustive_joint_reference``) and (b) the greedy_set pipeline.
Acceptance (issue 5): joint with hungarian pairing matches the exhaustive
joint optimum on |N| <= 8 and is never slower than greedy_set per round.

Writes ``experiments/bench/BENCH_joint_selection.json`` (uploaded by the
CI engine-bench job).
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

from repro.configs import SELECTIONS, FLConfig, NOMAConfig
from repro.core import RoundEnv, aoi, noma, plan, schedule_age_noma

PAIRINGS_MEASURED = ("strong_weak", "hungarian")


def _make_env(rng, n, ncfg):
    d = noma.sample_distances(rng, n, ncfg)
    return RoundEnv(noma.sample_gains(rng, d, ncfg),
                    rng.integers(100, 1000, n).astype(float),
                    rng.uniform(0.5e9, 2e9, n), aoi.init_ages(n), 4e6)


def run(out_dir="experiments/bench", trials=200, seed=0, smoke=False,
        out=None):
    if smoke:
        trials = min(trials, 30)
    rows = []
    for n in (4, 6, 8, 16):
        # slots < n so the admitted set is a real decision variable
        ncfg = NOMAConfig(n_subchannels=max(n // 4, 1))
        exhaustive = n <= plan.JOINT_ENUM_MAX_N
        rng = np.random.default_rng(seed)
        t = {(p, s): [] for p in PAIRINGS_MEASURED for s in SELECTIONS}
        opts = []
        for _ in range(trials):
            env = _make_env(rng, n, ncfg)
            for p in PAIRINGS_MEASURED:
                for s in SELECTIONS:
                    cfg = FLConfig(pairing=p, selection=s)
                    t[(p, s)].append(
                        schedule_age_noma(env, ncfg, cfg).t_round)
            if exhaustive:
                opts.append(plan.exhaustive_joint_reference(
                    env, ncfg, FLConfig()))
        t = {k: np.asarray(v) for k, v in t.items()}
        opts = np.asarray(opts) if exhaustive else None
        for p in PAIRINGS_MEASURED:
            for s in SELECTIONS:
                greedy = t[(p, "greedy_set")]
                row = {"n_clients": n, "pairing": p, "selection": s,
                       "t_round_mean_s": float(t[(p, s)].mean()),
                       "vs_greedy_mean": float(
                           (t[(p, s)] / greedy).mean()),
                       "vs_greedy_max": float(
                           (t[(p, s)] / greedy).max())}
                if exhaustive:
                    r = t[(p, s)] / np.maximum(opts, 1e-12)
                    row.update({"ratio_mean": float(r.mean()),
                                "ratio_p95": float(np.percentile(r, 95)),
                                "ratio_max": float(r.max()),
                                "optimal_frac": float(
                                    np.mean(r < 1.0 + 1e-9))})
                rows.append(row)
    os.makedirs(out_dir, exist_ok=True)
    path = out or os.path.join(out_dir, "BENCH_joint_selection.json")
    with open(path, "w") as f:
        json.dump(rows, f, indent=1, allow_nan=False)
    print("name,n_clients,pairing,selection,ratio_mean,ratio_max,"
          "vs_greedy_mean,vs_greedy_max")
    for r in rows:
        print(f"joint_selection,{r['n_clients']},{r['pairing']},"
              f"{r['selection']},"
              f"{r.get('ratio_mean', float('nan')):.4f},"
              f"{r.get('ratio_max', float('nan')):.4f},"
              f"{r['vs_greedy_mean']:.4f},{r['vs_greedy_max']:.4f}")
    return rows


if __name__ == "__main__":
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                           / "src"))
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    run(trials=args.trials, seed=args.seed, smoke=args.smoke, out=args.out)
