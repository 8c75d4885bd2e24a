"""End-to-end FL training driver — the paper's experiment.

Runs federated training of a (reduced or full) assigned architecture over
the simulated NOMA cell with a selectable scheduling policy, logging
accuracy vs. rounds AND vs. simulated wall-clock (the paper's key axes).

    PYTHONPATH=src python -m repro.launch.train --arch smollm_135m \
        --policy age_noma --rounds 60 --clients 30 [--full-size]
        [--ckpt-dir ckpts/run0] [--out experiments/fl]

(The full-size configs are for real TPU deployments; on this CPU container
use the default reduced variants.)
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

from repro.configs import ARCH_IDS, FLConfig, NOMAConfig, get_config
from repro.data import TaskConfig, bayes_optimal_accuracy
from repro.fl import FLServer
from repro import checkpoint as ckpt
from repro.launch import compile_cache


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_135m", choices=ARCH_IDS)
    ap.add_argument("--policy", default="age_noma_budget",
                    choices=["age_noma", "age_noma_budget", "random",
                             "channel", "round_robin", "oma_age"])
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--clients", type=int, default=30)
    ap.add_argument("--subchannels", type=int, default=5)
    ap.add_argument("--local-epochs", type=int, default=1)
    ap.add_argument("--local-batch", type=int, default=16)
    ap.add_argument("--lr", type=float, default=0.3)
    ap.add_argument("--alpha", type=float, default=0.3,
                    help="Dirichlet non-IID concentration")
    ap.add_argument("--age-exponent", type=float, default=1.0)
    ap.add_argument("--t-budget", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full-size", action="store_true",
                    help="use the full assigned config (TPU scale)")
    ap.add_argument("--eval-every", type=int, default=5)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--out", default="experiments/fl")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if not args.full_size:
        cfg = dataclasses.replace(cfg.reduced(), d_model=64, d_ff=128,
                                  vocab_size=64)
    fl = FLConfig(n_clients=args.clients, rounds=args.rounds,
                  local_epochs=args.local_epochs,
                  local_batch=args.local_batch, lr=args.lr,
                  dirichlet_alpha=args.alpha, policy=args.policy,
                  age_exponent=args.age_exponent, t_budget_s=args.t_budget,
                  samples_per_client=(64, 192), seed=args.seed)
    nomacfg = NOMAConfig(n_subchannels=args.subchannels)
    task = TaskConfig(vocab_size=min(cfg.vocab_size, 64), n_topics=8,
                      seq_len=33, seed=args.seed)

    print(f"[train] arch={args.arch} policy={args.policy} "
          f"clients={args.clients} rounds={args.rounds}")
    print(f"[train] bayes-optimal accuracy ceiling: "
          f"{bayes_optimal_accuracy(task):.4f}")
    server = FLServer(cfg, fl, nomacfg, task, policy=args.policy,
                      eval_every=args.eval_every, seed=args.seed)
    t0 = time.time()
    hist = server.run(args.rounds, verbose=True)
    wall = time.time() - t0
    print(f"[train] done in {wall:.1f}s wall; simulated t={server.t_sim:.1f}s"
          f"; final acc={hist.accuracy[-1]:.4f}")

    if args.ckpt_dir:
        path = ckpt.save(args.ckpt_dir, server.params,
                         step=server.round_idx,
                         extra={"policy": args.policy, "arch": args.arch})
        print(f"[train] checkpoint -> {path}")

    os.makedirs(args.out, exist_ok=True)
    tag = f"{args.arch}__{args.policy}__s{args.seed}"
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump({"args": vars(args), "history": hist.as_dict(),
                   "wall_s": wall}, f, indent=1, allow_nan=False)
    print(f"[train] history -> {args.out}/{tag}.json")


if __name__ == "__main__":
    main()
