"""Readings that set a cell's limits, on the chip at the cell's own size:
for each seed, in one process, the program against the reference (the
lower reading) and the control, the reference computed in the precision
below the configuration's (bfloat16 for float32), against the reference
(the upper reading). ``--fault`` plants one of ``bench/faults.py`` under
the timed path and reads the program instead. The benchmark's own runs do
not run this.

    python bench/control.py --workload <cell> --seeds 1 2 3 [--units 3]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import types

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import compare  # noqa: E402
from bench import run as harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--units", type=int, default=3,
                    help="calls or rounds after set-up before reading")
    ap.add_argument("--fault", default=None)
    a = ap.parse_args()
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    c = harness.load_cell(a.workload, spec)
    harness.prepare(c.chips)
    import importlib

    import jax.numpy as jnp
    mod = importlib.import_module(f"bench.drivers.{c.traffic['driver']}")
    undo = None
    if a.fault:
        from bench import faults
        target, attr, repl = getattr(faults, c.traffic["driver"])(a.fault)
        undo = (target, attr, getattr(target, attr))
        setattr(target, attr, repl)
    for seed in a.seeds:
        ctx = types.SimpleNamespace(config=c.config, traffic=c.traffic,
                                    cell=c.cell, seed=seed, name=a.workload)
        drv = mod.Driver(ctx)
        drv.setup()
        for _ in range(a.units):
            drv.run_unit()
        drv.close()
        out = {"seed": seed, "program": drv.numbers()}
        if not a.fault:
            out["control"] = drv.numbers(jnp.bfloat16)
        print(json.dumps(compare.finite(out), allow_nan=False), flush=True)
    if undo is not None:
        setattr(*undo)


if __name__ == "__main__":
    main()
