"""The harness end to end on the CPU at tiny sizes: a run's result line,
a cell added as files only, and the refusals (no TPU, no program)."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from _bench_cells import FL, MC, REPO, run_tiny, spec, write_tiny

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return write_tiny(tmp_path_factory.mktemp("cells"))


@pytest.mark.parametrize("workload,metrics", [
    (MC, {"drops_per_s", "sweep_p95_s", "setup_s"}),
    (FL, {"fl_round_wall_s", "setup_s"}),
])
def test_result_line(tiny, workload, metrics):
    res = run_tiny(tiny, workload)
    assert list(res) == KEYS
    json.dumps(res, allow_nan=False)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == metrics
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    for chk in res["checks"].values():
        assert chk["value"] <= chk["limit"]


def test_new_cell_added_as_files_only(tiny, tmp_path):
    """A cell, configuration and traffic mix that exist only as new files
    (and a new BENCHMARK.json entry) run without an edit to any file."""
    base = tmp_path / "bench_files"
    shutil.copytree(tiny, base)
    cfg = json.loads((base / "configs"
                      / "noma_vehicular_n100k_k5.json").read_text())
    cfg["name"] = "noma_tiny_k3"
    cfg["deployment"].update(n_clients=500, n_subchannels=3)
    (base / "configs" / "noma_tiny_k3.json").write_text(json.dumps(cfg, allow_nan=False))
    tr = json.loads((base / "traffic" / "mc_age_sw.json").read_text())
    tr.update(n_seeds=4, rounds=2)
    (base / "traffic" / "mc_tiny.json").write_text(json.dumps(tr, allow_nan=False))
    name = "mc.tiny_k3.age_sw"
    cell = json.loads((base / "cells" / f"{MC}.json").read_text())
    cell.update(config="noma_tiny_k3", traffic="mc_tiny")
    (base / "cells" / f"{name}.json").write_text(json.dumps(cell, allow_nan=False))
    s = spec()
    s["workloads"].append({"name": name, "config": "noma_tiny_k3",
                           "traffic": "mc_tiny", "chips": 1, "why": "test"})
    for m in s["end_to_end"] + s["per_layer"]:
        if MC in m.get("workloads", []):
            m["workloads"].append(name)
    sys.path.insert(0, str(REPO))
    from bench import run as harness
    from _bench_cells import isolated_jax
    with isolated_jax():
        res = harness.run(name, 11, 0.5, False, require_chip=False, spec=s,
                          base=base)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"drops_per_s", "sweep_p95_s", "setup_s"}


def _cli(cwd, *extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", MC, "--seed",
         str(2 ** 40 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    p = _cli(REPO)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip().endswith("}")


def test_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for d in spec()["paths"]:
        shutil.copytree(REPO / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_missing_program_hook_is_named(tiny, monkeypatch):
    """The FL driver records rounds through named hooks of the program;
    one that is gone stops the run with its name."""
    sys.path.insert(0, str(REPO / "src"))
    import repro.fl.server as server_mod
    monkeypatch.delattr(server_mod, "aggregate_deltas")
    with pytest.raises(SystemExit, match="module.aggregate_deltas is missing"):
        run_tiny(tiny, FL, seconds=0.5)
