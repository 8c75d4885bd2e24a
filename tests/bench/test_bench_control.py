"""The control of each cell, the reference computed in bfloat16 in the
program's place, comes out not correct against the cell's limits (here at
a tiny size; ``bench/control.py`` reads it on the chip at the cell's own
size)."""
from __future__ import annotations

import json
import sys
import types

import pytest

from _bench_cells import FL, MC, REPO, isolated_jax, spec, write_tiny

sys.path.insert(0, str(REPO))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return write_tiny(tmp_path_factory.mktemp("cells"))


@pytest.mark.parametrize("workload,units", [(MC, 2), (FL, 0)])
def test_control_fails_and_program_passes(tiny, workload, units):
    import importlib

    import jax.numpy as jnp

    from bench import compare
    from bench import run as harness
    with isolated_jax():
        c = harness.load_cell(workload, spec(), tiny)
        harness.prepare(c.chips, require_chip=False)
        drv = importlib.import_module(
            f"bench.drivers.{c.traffic['driver']}").Driver(
            types.SimpleNamespace(config=c.config, traffic=c.traffic,
                                  cell=c.cell, seed=2 ** 35 + 1,
                                  name=workload))
        drv.setup()
        for _ in range(units):
            drv.run_unit()
        drv.close()
        limits = json.loads((tiny / "cells" / f"{workload}.json")
                            .read_text())["limits"]
        program = compare.judge(drv.numbers(), limits)
        control = compare.judge(drv.numbers(jnp.bfloat16), limits)
    assert all(ok for *_, ok in program), program
    assert not all(ok for *_, ok in control), control
