import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf hillclimb runner: compile a named variant list for one
(arch x shape) pair and tabulate the roofline deltas.

    PYTHONPATH=src python -m repro.launch.perf --pair grok_train
"""

import argparse
import contextlib
import json

from repro.launch.dryrun import dryrun_one

# hypothesis -> change, per hillclimb pair (see EXPERIMENTS.md §Perf for
# the napkin math and the confirmed/refuted log)
PAIRS = {
    "grok_train": {
        "arch": "grok_1_314b", "shape": "train_4k",
        "variants": [
            {"note": "baseline"},
            {"note": "bf16_accum", "accum_dtype": "bfloat16"},
            {"note": "act_model_shard", "act_model_shard": True},
            {"note": "bf16+actshard", "accum_dtype": "bfloat16",
             "act_model_shard": True},
        ],
    },
    "llama4_prefill": {
        "arch": "llama4_maverick_400b_a17b", "shape": "prefill_32k",
        "variants": [
            {"note": "baseline"},
            {"note": "ring_attn", "ring_attn": True},
        ],
    },
    "smollm_train": {
        "arch": "smollm_135m", "shape": "train_4k",
        "variants": [
            {"note": "baseline"},
            {"note": "micro1", "micro": 1},
            {"note": "bf16_accum", "accum_dtype": "bfloat16"},
            {"note": "actshard", "act_model_shard": True},
        ],
    },
}


def run_pair(name: str, out_dir: str = "experiments/perf", *,
             profile_dir: str = None):
    """Run one hillclimb pair. ``profile_dir`` wraps the variant sweep in
    the opt-in ``jax.profiler.trace`` hook (``obs.trace.profile``) and each
    variant compile in a host span — inspect with ``tensorboard --logdir``
    and ``trace.format_report``."""
    from repro.obs import trace

    spec = PAIRS[name]
    rows = []
    prof = (trace.profile(profile_dir) if profile_dir
            else contextlib.nullcontext())
    with prof:
        for variant in spec["variants"]:
            with trace.span("perf.variant", pair=name,
                            note=variant.get("note", "")):
                rec = dryrun_one(spec["arch"], spec["shape"],
                                 variant=variant)
            rows.append(rec)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{name}.json"), "w") as f:
        json.dump(rows, f, indent=1, allow_nan=False)
    print(f"\n{'variant':24s} {'mem/chip':>9s} {'t_c_s':>8s} {'t_m_s':>8s} "
          f"{'t_floor':>8s} {'t_l_s':>8s}")
    for r in rows:
        print(f"{r['note']:24s} {r['memory_per_chip']/2**30:8.2f}G "
              f"{r['t_compute']:8.2f} {r['t_memory']:8.2f} "
              f"{r['t_memory_floor']:8.3f} {r['t_collective']:8.2f}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", required=True, choices=list(PAIRS))
    ap.add_argument("--out", default="experiments/perf")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="dump a jax.profiler trace of the sweep to DIR "
                         "(view with tensorboard --logdir DIR)")
    args = ap.parse_args()
    run_pair(args.pair, args.out, profile_dir=args.profile)


if __name__ == "__main__":
    main()
