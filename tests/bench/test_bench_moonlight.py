"""The Moonlight cell end to end on the CPU at a tiny size: the real cell,
traffic and configuration files with the model, population and traffic
shrunk (every kind of layer kept: the dense layer, latent attention, held
and shared experts, a held share that does not start at expert 0), run
through the harness; its result line, the readers of its per-layer
metrics, the bfloat16 control and each fault of the SGD step or the
client's batches coming out not correct."""
from __future__ import annotations

import json
import sys
import types

import pytest

from _bench_cells import REPO, isolated_jax, run_tiny, spec, write_tiny

sys.path.insert(0, str(REPO))

CELL = "fl.moonlight_16b_a3b.cohort10"


def _load(kind: str, name: str) -> dict:
    return json.loads((REPO / "bench" / kind / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    base = write_tiny(tmp_path_factory.mktemp("cells"))
    w = next(x for x in spec()["workloads"] if x["name"] == CELL)
    cfg, tr = _load("configs", w["config"]), _load("traffic", w["traffic"])
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
               v_head_dim=8, intermediate_size=128, moe_intermediate_size=32,
               num_hidden_layers=3, n_routed_experts=4, vocab_size=512)
    cfg["expert_parallel"].update(router_experts=16, first_held_expert=4)
    cfg["deployment"]["n_clients"] = 12
    tr.update(samples_per_client=[20, 60], local_batch=8)
    tr["task"].update(vocab_size=64, seq_len=9)
    for kind, name, obj in (("configs", w["config"], cfg),
                            ("traffic", w["traffic"], tr)):
        (base / kind / f"{name}.json").write_text(
            json.dumps(obj, allow_nan=False))
    return base


def test_result_line(tiny):
    res = run_tiny(tiny, CELL)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"fl_round_wall_s", "setup_s"}


def _readings(tiny):
    """What the harness hands a reader: the tiny config and traffic, the
    traced units' counts, a reduction with two ragged-dot kernels of the
    step (and their metadata op, not counted) on a v5e."""
    from bench import run as harness
    c = harness.load_cell(CELL, spec(), tiny)
    red = types.SimpleNamespace(window_s=2.0, op_s={
        "jit_step/ragged-dot-none.1": 0.004,
        "jit_step/ragged-dot-none": 0.006,
        "jit_step/ragged-dot-metadata.1": 1.0,
        "jit_step/fusion.3": 1.0})
    return types.SimpleNamespace(
        config=c.config, traffic=c.traffic, device_kind="TPU v5 lite",
        reduction=red, counts={"steps": 10, "moe_routed": 300,
                               "moe_rows": 1200})


@pytest.mark.parametrize("metric", ["moe_pad_share", "moe_gmm_roofline",
                                    "fl_moe_mfu"])
def test_metric_readers(tiny, metric):
    """Each new per-layer metric, read from the harness's inputs, equals
    its definition computed here by hand."""
    from bench import peaks
    from bench import run as harness
    from bench.costs import moonlight_sgd
    m = _readings(tiny)
    c = m.config
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    if metric == "moe_pad_share":
        want = 100.0 * (1 - 300 / 1200)
    elif metric == "moe_gmm_roofline":
        layer_steps = 10 * (c["num_hidden_layers"] - 1)
        flops = 18.0 * d * f * 300
        bytes_ = 9 * 4 * (c["n_routed_experts"] * d * f * layer_steps
                          + 300 * (d + f))
        want = 100.0 * max(flops / 197e12, bytes_ / 819e9) / 0.010
    else:
        seq = m.traffic["task"]["seq_len"] - 1
        flops = (10 * moonlight_sgd.step_flops(c, m.traffic["local_batch"],
                                               seq, 0.0)
                 + 300 * 18.0 * d * f)
        want = 100.0 * flops / (2.0 * peaks.peaks("TPU v5 lite").flops_per_s)
    assert harness.reader(metric, tiny)(m) == pytest.approx(want)
    m.counts = {}
    assert harness.reader(metric, tiny)(m) is None


def test_control_fails_and_program_passes(tiny):
    import importlib

    import jax.numpy as jnp

    from bench import compare
    from bench import run as harness
    with isolated_jax():
        c = harness.load_cell(CELL, spec(), tiny)
        harness.prepare(c.chips, require_chip=False)
        drv = importlib.import_module(
            f"bench.drivers.{c.traffic['driver']}").Driver(
            types.SimpleNamespace(config=c.config, traffic=c.traffic,
                                  cell=c.cell, seed=2 ** 35 + 3, name=CELL))
        drv.setup()
        drv.run_unit()
        drv.close()
        limits = c.cell["limits"]
        numbers = drv.numbers()
        program = compare.judge(numbers, limits)
        control = compare.judge(drv.numbers(jnp.bfloat16), limits)
    assert all(ok for *_, ok in program), program
    assert not all(ok for *_, ok in control), control
    # reported beside the limits, not judged: at these sizes the program's
    # top-k meets the reference's on every choice
    assert "routing_mismatch" not in limits
    assert numbers["routing_mismatch"] == 0.0


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "half_client_batches"])
def test_fault_is_caught(tiny, monkeypatch, kind):
    """The fl cell's faults of the SGD step and of the client's batches,
    planted under the Moonlight program's timed path, fail the cell."""
    from bench import faults
    monkeypatch.setattr(*faults.fl(kind))
    res = run_tiny(tiny, CELL, seconds=0.5)
    assert res["correct"] is False, res["checks"]
