"""Tiny copies of the benchmark's cells for the CPU tests: the real cell,
configuration and traffic files with the population, model and traffic
shrunk, written to a temporary directory the harness reads instead of
``bench/``."""
from __future__ import annotations

import contextlib
import json
import pathlib

REPO = pathlib.Path(__file__).resolve().parents[2]
FL = "fl.smollm_135m.cohort10"
MC = "mc.vehicular_n100k_k5.age_sw"


def spec() -> dict:
    return json.loads((REPO / "BENCHMARK.json").read_text())


def _load(kind: str, name: str) -> dict:
    return json.loads((REPO / "bench" / kind / f"{name}.json").read_text())


def write_tiny(base: pathlib.Path) -> pathlib.Path:
    for kind in ("cells", "configs", "traffic"):
        (base / kind).mkdir(parents=True, exist_ok=True)
    put = lambda kind, name, obj: (base / kind / f"{name}.json").write_text(
        json.dumps(obj, indent=2, allow_nan=False))
    for w in spec()["workloads"]:
        cell = _load("cells", w["name"])
        cfg = _load("configs", w["config"])
        tr = _load("traffic", w["traffic"])
        if tr["driver"] == "fl":
            cfg.update(hidden_size=64, intermediate_size=128,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, vocab_size=512)
            cfg["deployment"]["n_clients"] = 12
            tr.update(samples_per_client=[20, 60], local_batch=8)
            tr["task"].update(vocab_size=64, seq_len=9)
        else:
            cfg["deployment"]["n_clients"] = 3000
            tr.update(n_seeds=8, rounds=3, sample_from_first=4,
                      sample_calls=2)
        put("cells", w["name"], cell)
        put("configs", w["config"], cfg)
        put("traffic", w["traffic"], tr)
    return base


@contextlib.contextmanager
def isolated_jax():
    """The harness turns JAX's persistent cache on; give the test
    process's other tests their settings back."""
    import jax
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    try:
        yield
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)


def run_tiny(base, workload, *, seconds=1.0, trace=False, seed=2 ** 33 + 7):
    import sys
    sys.path.insert(0, str(REPO))
    from bench import run as harness
    with isolated_jax():
        return harness.run(workload, seed, seconds, trace,
                           require_chip=False, spec=spec(), base=base)
