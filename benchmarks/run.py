"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick|--smoke] [--only NAME]

Prints ``name,...`` CSV rows; writes JSON artifacts to experiments/bench/
(or ``--out-dir DIR`` — CI uses a scratch dir so smoke numbers never
overwrite the committed full-run baselines, then gates them with
``benchmarks/regress.py``). Each sweep also appends a JSONL run ledger
under experiments/runs/ (disable with REPRO_LEDGER=0).
``--smoke`` is the CI alias of ``--quick``; ``--check-registry`` verifies
(without running anything) that every ``benchmarks/*.py`` module is
registered in ``BENCHES`` — the engine-bench CI job runs it so a new
benchmark module cannot silently miss the harness.
Claim mapping (DESIGN.md section 1):
    C1 fl_convergence      accuracy vs rounds/time per policy
    C2 noma_vs_oma         round-time NOMA vs OMA
    C3 fairness_age        staleness + participation fairness
    C4 pairing_optimality  heuristic vs exhaustive pairing
    C5 predictor_gain      ANN update predictor vs stale-reuse vs none
       joint_selection     joint vs greedy_set selection vs the exhaustive
                           joint (set x matching) optimum
       kernels             Pallas-kernel micro-benches
       roofline            dry-run derived roofline table
       engine_throughput   batched wireless engine drops/sec vs numpy
       admission_scaling   full_sort vs segmented admission drops/sec vs N
       scenario_throughput fused vs pre-sampled scenario stepping
       multicell_scaling   single-cell vs C-cell drops/sec at fixed N
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time
import traceback

from benchmarks import (
    admission_scaling,
    engine_throughput,
    fairness_age,
    fl_convergence,
    joint_selection,
    kernels_bench,
    multicell_scaling,
    noma_vs_oma,
    pairing_optimality,
    predictor_gain,
    roofline_table,
    scenario_throughput,
)
from repro.launch import compile_cache

# every entry takes (quick, out): ``out`` is the JSON output path, or None
# for each module's default under experiments/bench/
BENCHES = {
    "engine_throughput": lambda quick, out: engine_throughput.run(
        smoke=quick, out_path=out),
    "admission_scaling": lambda quick, out: admission_scaling.run(
        smoke=quick, out_path=out),
    "scenario_throughput": lambda quick, out: scenario_throughput.run(
        smoke=quick, out_path=out),
    "multicell_scaling": lambda quick, out: multicell_scaling.run(
        smoke=quick, out_path=out),
    "noma_vs_oma": lambda quick, out: noma_vs_oma.run(
        smoke=quick, out_path=out),
    "fairness_age": lambda quick, out: fairness_age.run(
        smoke=quick, out_path=out),
    "pairing_optimality": lambda quick, out: pairing_optimality.run(
        smoke=quick, out=out),
    "joint_selection": lambda quick, out: joint_selection.run(
        smoke=quick, out=out),
    "kernels": lambda quick, out: kernels_bench.run(
        smoke=quick, out_path=out),
    "fl_convergence": lambda quick, out: fl_convergence.run(
        smoke=quick, out_path=out),
    "predictor_gain": lambda quick, out: predictor_gain.run(
        smoke=quick, out_path=out),
    "roofline": lambda quick, out: roofline_table.run(
        out_dir=os.path.dirname(out) if out else "experiments/bench"),
}

# modules in benchmarks/ that are not benchmarks themselves
_NON_BENCH = {"run", "__init__", "regress"}
# registry name -> module name where they differ
_ALIASES = {"kernels": "kernels_bench", "roofline": "roofline_table"}


def check_registry() -> None:
    """Every benchmarks/*.py module must be registered in BENCHES (so
    ``--smoke`` exercises all of them). Exits non-zero on a miss."""
    here = pathlib.Path(__file__).resolve().parent
    modules = {p.stem for p in here.glob("*.py")} - _NON_BENCH
    registered = {_ALIASES.get(name, name) for name in BENCHES}
    missing = sorted(modules - registered)
    stale = sorted(registered - modules)
    if missing or stale:
        print(f"benchmark registry mismatch: missing={missing} "
              f"stale={stale}")
        sys.exit(1)
    print(f"benchmark registry ok: {len(BENCHES)} benchmarks registered, "
          f"{len(modules)} modules on disk")


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="alias of --quick (CI naming)")
    ap.add_argument("--only", default=None, choices=list(BENCHES))
    ap.add_argument("--out-dir", default=None, metavar="DIR",
                    help="write BENCH_*.json here instead of "
                         "experiments/bench/ (CI scratch dir)")
    ap.add_argument("--check-registry", action="store_true",
                    help="verify every benchmarks/*.py module is "
                         "registered, run nothing")
    args = ap.parse_args()
    if args.check_registry:
        check_registry()
        return
    quick = args.quick or args.smoke
    if args.out_dir:
        os.makedirs(args.out_dir, exist_ok=True)

    from repro.obs import RunLedger

    failed = []
    ledger = RunLedger.open("bench_suite", {
        "quick": quick, "only": args.only, "out_dir": args.out_dir})
    try:
        for name, fn in BENCHES.items():
            if args.only and name != args.only:
                continue
            out = (os.path.join(args.out_dir, f"BENCH_{name}.json")
                   if args.out_dir else None)
            t0 = time.time()
            print(f"# === {name} ===", flush=True)
            try:
                fn(quick, out)
                ok = True
            except Exception:  # noqa: BLE001
                traceback.print_exc()
                failed.append(name)
                ok = False
            wall = time.time() - t0
            ledger.event("bench", name=name, ok=ok, wall_s=round(wall, 3))
            print(f"# {name} done in {wall:.1f}s", flush=True)
    finally:
        ledger.close()
    if failed:
        print("FAILED:", failed)
        sys.exit(1)


if __name__ == "__main__":
    main()
