"""The trace reduction: busy union, idle gaps and their host spans, time
per module and per op, on hand-made events and on a small recorded chip
trace (one TPU v5e, two ``run_montecarlo`` sweeps of 8 drops x 2,000
clients x 2 rounds inside a ``bench.window`` annotation, trimmed to the
device's module and op lines and the host's python line)."""
from __future__ import annotations

import pathlib
import sys

import pytest

from _bench_cells import REPO

sys.path.insert(0, str(REPO))

from bench import trace_reduce as T  # noqa: E402

FIXTURE = pathlib.Path(__file__).parent / "data" / "mc_tiny.xplane.pb"
E = T.Event


def test_union_merges_overlaps_and_clips():
    u = T.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert T.clip(u, 1, 6) == [(1, 3), (5, 6)]


def test_reduce_hand_made():
    tr = T.Trace(
        modules=[[E("jit_a", 0, 40), E("jit_b", 60, 30)]],
        ops=[[E("fusion.1", 0, 30), E("fusion.2", 20, 20),
              E("fusion.1", 60, 30)]],
        host=[E("bench.window", 0, 100), E("bench.call", 0, 95),
              E("engine.mc_loop", 45, 10)])
    red = T.reduce(tr)
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx(70e-9)
    assert red.idle_share == pytest.approx(0.3)
    assert red.module_time("^jit_a$") == (pytest.approx(40e-9), 1)
    assert red.op_s["fusion.1"] == pytest.approx(60e-9)
    # gaps: [40, 60) under the mc_loop span, [90, 100) under bench.call
    assert red.gaps[0] == (pytest.approx(20e-9), "engine.mc_loop")
    assert red.gaps[1] == (pytest.approx(10e-9), "bench.call")
    tr.host = [E("bench.window", 0, 100)]
    assert T.reduce(tr).gaps[0][1] == "bench.window"
    b = T.breakdown(red)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(60e-9)]
    assert b["idle_gaps"][0][0] == "engine.mc_loop"


def test_ops_are_attributed_to_their_module():
    mods = [E("jit_a", 0, 10), E("jit_b", 20, 10)]
    ops = [E("fusion.1", 1, 2), E("fusion.1", 21, 2), E("copy", 15, 1)]
    names = [e.name for e in T.attribute(ops, mods)]
    assert names == ["jit_a/fusion.1", "jit_b/fusion.1", "(no module)/copy"]


def test_names_are_shortened():
    assert T.op_base("%fusion.6 = f32[64,100000,2]{1,2,0} fusion(...)") \
        == "fusion.6"
    assert T.module_base("jit__montecarlo_step(14728949555235052224)") \
        == "jit__montecarlo_step"


def test_recorded_chip_trace():
    tr = T.load(str(FIXTURE))
    assert len(tr.ops) == 1 and tr.ops[0]
    red = T.reduce(tr)
    assert 0.0 < red.busy_s < red.window_s
    assert 0.0 < red.idle_share < 1.0
    step_s, step_n = red.module_time(r"^jit__montecarlo_step$")
    scen_s, scen_n = red.module_time(r"^jit__step_core$")
    assert step_n == 4 and scen_n == 4          # 2 sweeps x 2 rounds
    assert 0.0 < step_s < red.busy_s and 0.0 < scen_s < red.busy_s
    assert red.gaps and all(g > 0 for g, _ in red.gaps)
    assert all(name for _, name in red.gaps)
    assert sum(red.op_s.values()) >= red.busy_s * 0.999
    assert any(k.startswith("jit__montecarlo_step/") for k in red.op_s)
