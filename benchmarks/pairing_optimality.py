"""Paper table analogue (claim C4): pairing policies + closed-form power vs
the exhaustive-optimal pairing.

Per instance size (4/6/8 clients — the exhaustive reference's range) and
per ``FLConfig.pairing`` policy this measures the scheduled round time
against (a) the exhaustive optimum over ALL pairings and (b) the paper's
strong_weak heuristic. A larger no-reference size tracks the policy axis
where brute force can't follow. Acceptance (issue 4): hungarian within 1%
of the optimum and never slower than strong_weak.

Writes ``experiments/bench/BENCH_pairing_optimality.json`` (uploaded by the
CI engine-bench job).
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

from repro.configs import PAIRINGS, FLConfig, NOMAConfig
from repro.core import (
    RoundEnv,
    aoi,
    exhaustive_pairing_reference,
    noma,
    schedule_age_noma,
)


def _make_env(rng, n, ncfg):
    d = noma.sample_distances(rng, n, ncfg)
    return RoundEnv(noma.sample_gains(rng, d, ncfg),
                    rng.integers(100, 1000, n).astype(float),
                    rng.uniform(0.5e9, 2e9, n), aoi.init_ages(n), 4e6)


def run(out_dir="experiments/bench", trials=200, seed=0, smoke=False,
        out=None):
    if smoke:
        trials = min(trials, 30)
    cfgs = {p: FLConfig(pairing=p) for p in PAIRINGS}
    rows = []
    for n in (4, 6, 8, 20):
        ncfg = NOMAConfig(n_subchannels=min(n, 20) // 2)
        exhaustive = n <= 8
        rng = np.random.default_rng(seed)
        t = {p: [] for p in PAIRINGS}
        opts = []
        for _ in range(trials):
            env = _make_env(rng, n, ncfg)
            for p in PAIRINGS:
                t[p].append(schedule_age_noma(env, ncfg, cfgs[p]).t_round)
            if exhaustive:
                opts.append(exhaustive_pairing_reference(
                    list(range(n)), env, ncfg, cfgs["strong_weak"]))
        t = {p: np.asarray(v) for p, v in t.items()}
        opts = np.asarray(opts) if exhaustive else None
        for p in PAIRINGS:
            row = {"n_clients": n, "policy": p,
                   "t_round_mean_s": float(t[p].mean()),
                   "vs_strong_weak_mean": float(
                       (t[p] / t["strong_weak"]).mean()),
                   "vs_strong_weak_max": float(
                       (t[p] / t["strong_weak"]).max())}
            if exhaustive:
                r = t[p] / np.maximum(opts, 1e-12)
                row.update({"ratio_mean": float(r.mean()),
                            "ratio_p95": float(np.percentile(r, 95)),
                            "ratio_max": float(r.max()),
                            "optimal_frac": float(
                                np.mean(r < 1.0 + 1e-9))})
            rows.append(row)
    os.makedirs(out_dir, exist_ok=True)
    path = out or os.path.join(out_dir, "BENCH_pairing_optimality.json")
    with open(path, "w") as f:
        json.dump(rows, f, indent=1, allow_nan=False)
    print("name,n_clients,policy,ratio_mean,ratio_max,vs_sw_mean,vs_sw_max")
    for r in rows:
        print(f"pairing_optimality,{r['n_clients']},{r['policy']},"
              f"{r.get('ratio_mean', float('nan')):.4f},"
              f"{r.get('ratio_max', float('nan')):.4f},"
              f"{r['vs_strong_weak_mean']:.4f},"
              f"{r['vs_strong_weak_max']:.4f}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    run(trials=args.trials, seed=args.seed, smoke=args.smoke, out=args.out)
