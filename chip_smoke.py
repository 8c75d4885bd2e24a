"""Bring-up check on a TPU: both halves of the system, through their normal
entry points, at real size, each compiled path compared with its XLA twin
on the chip.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the sharded sweep on a 4-chip host

Phases on one chip:
  device   JAX's first device must be a TPU; there is no CPU path.
  kernels  ``resolve_backend("auto")`` gives compiled Mosaic kernels;
           ``planner_tables`` and ``pairscore`` at c in {10, 256},
           B in {1, 64}, and ``fedagg`` at C=10 over a smollm-sized leaf
           and C=50, each against its XLA twin at the tiers of
           ``tests/test_kernels.py``.
  sweep    ``run_montecarlo`` at N=10,000 clients, 64 seeds, 8 rounds,
           vehicular, K=5, under ``strong_weak`` and ``hungarian`` pairing,
           compiled kernels against the XLA twin: identical selections,
           round times within the tier of ``tests/test_backend.py``.
  fl       ``FLServer`` training ``smollm_135m`` at its published widths
           for 3 rounds with the compiled planner and fedagg kernels; every
           round's aggregate is recomputed by the XLA twin from the same
           deltas and compared.

``--four-chips`` runs only the sharded sweep: ``run_montecarlo(shard=True)``
over 4 devices against the same call on one.

Per-phase wall times and peak device memory go to earlier lines, as
information. The last line of stdout is one JSON object naming the device;
any failed check exits non-zero before it is printed.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

SWEEP = dict(n_clients=10_000, n_seeds=64, rounds=8, scenario="vehicular",
             policies=("age_noma",))
K = 5
PLANNER_SHAPES = ((1, 10), (1, 256), (64, 10), (64, 256))     # (B, c)
FEDAGG_SHAPES = ((10, 49_152 * 576), (50, 576 * 1536))       # (C, N)
EPS32 = 2.0 ** -23


def model_config():
    """``smollm_135m`` at its published widths: 30 layers, d_model 576,
    9/3 heads, d_ff 1536, vocab 49,152."""
    from repro.configs import get_config
    return get_config("smollm_135m")


class Checks:
    """Collects one phase's comparisons, printing each; ``done`` fails the
    phase if any of them failed."""

    def __init__(self, phase: str):
        self.phase = phase
        self.failed = []

    def true(self, name: str, ok: bool, detail: str = ""):
        print(f"  {'ok  ' if ok else 'FAIL'} {name} {detail}", flush=True)
        if not ok:
            self.failed.append(name)

    def close(self, name: str, got, want, *, rtol: float, atol: float = 0.0):
        import numpy as np
        got = np.asarray(got, np.float64)
        want = np.asarray(want, np.float64)
        err = np.abs(got - want)
        ok = (got.shape == want.shape and bool(np.all(np.isfinite(got)))
              and bool(np.all(err <= atol + rtol * np.abs(want))))
        rel = float(np.max(err / np.maximum(np.abs(want), 1e-30)))
        self.true(name, ok, f"max_abs={float(np.max(err)):.3e} "
                  f"max_rel={rel:.3e} (rtol={rtol:g}, atol={atol:g})")

    def equal(self, name: str, got, want):
        import numpy as np
        got, want = np.asarray(got), np.asarray(want)
        ok = got.shape == want.shape and bool(np.array_equal(got, want))
        self.true(name, ok, f"shape={got.shape}")

    def done(self):
        if self.failed:
            sys.exit(f"chip_smoke: phase {self.phase} failed: {self.failed}")


@contextlib.contextmanager
def phase(name: str, device):
    print(f"[{name}]", flush=True)
    t0 = time.perf_counter()
    checks = Checks(name)
    yield checks
    checks.done()
    peak = device.memory_stats().get("peak_bytes_in_use", 0)
    print(f"[{name}] passed in {time.perf_counter() - t0:.1f} s; "
          f"peak_bytes_in_use={peak} ({peak / 2**30:.2f} GiB)", flush=True)


def kernels(ck: Checks):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import NOMAConfig
    from repro.kernels import ops, pairscore, planner
    from repro.kernels.backend import resolve_backend
    from repro.kernels.ref import weighted_sum_ref

    spec = resolve_backend("auto")
    ck.true("resolve_backend('auto') is compiled Mosaic",
            (spec.impl, spec.flavor) == ("pallas", "mosaic"), str(spec))
    ncfg = NOMAConfig()
    kw = dict(n0b=ncfg.noise_density * ncfg.bandwidth_hz,
              pmax=ncfg.max_power_w, bw=ncfg.bandwidth_hz)
    rng = np.random.default_rng(0)
    for b, c in PLANNER_SHAPES:
        g = np.sort(rng.uniform(1e-14, 1e-10, (b, c)), axis=-1)[:, ::-1]
        g = jnp.asarray(g.copy(), jnp.float32)
        tc = jnp.asarray(rng.uniform(0.05, 0.5, (b, c)), jnp.float32)
        tab, rm, sw = planner.planner_tables(g, tc, 4e6, impl="pallas",
                                             **kw)
        tab_x, rm_x, sw_x = planner.planner_tables(g, tc, 4e6,
                                                   impl="xla", **kw)
        ck.true(f"planner b={b} c={c} table is bf16",
                tab.dtype == jnp.bfloat16)
        ck.close(f"planner b={b} c={c} table", tab, tab_x, rtol=1e-2)
        ck.close(f"planner b={b} c={c} row_min", rm, rm_x, rtol=1e-6)
        ck.close(f"planner b={b} c={c} t_sw", sw, sw_x, rtol=1e-6)
        gi = jnp.broadcast_to(g[:, :, None], (b, c, c))
        gj = jnp.broadcast_to(g[:, None, :], (b, c, c))
        got = pairscore.pair_alloc_rates(gi, gj, impl="pallas", **kw)
        want = pairscore.pair_alloc_rates(gi, gj, impl="xla", **kw)
        for name, x, y in zip(("p_i", "p_j", "r_i", "r_j"), got, want):
            ck.close(f"pairscore b={b} c={c} {name}", x, y, rtol=1e-6,
                     atol=1e-9)
    # fedagg: fp32 summation tier of tests/test_kernels.py — kernel and
    # twin each lie within c * eps * sum|w u| of the exact sum
    key = jax.random.PRNGKey(0)
    for c, n in FEDAGG_SHAPES:
        u = jax.random.normal(jax.random.fold_in(key, c), (c, n))
        w = jax.random.uniform(jax.random.fold_in(key, c + 1), (c,))
        got = ops.weighted_sum(u, w, impl="pallas")
        want = ops.weighted_sum(u, w, impl="xla")
        bound = 2 * c * EPS32 * weighted_sum_ref(jnp.abs(u), w)
        err = jnp.abs(got - want)
        ck.true(f"fedagg C={c} N={n}",
                bool(jnp.all(jnp.isfinite(got)) & jnp.all(err <= bound)),
                f"max_abs={float(jnp.max(err)):.3e} "
                f"max_err/bound={float(jnp.max(err / bound)):.3e}")
        del u, got, want, bound, err


def sweep(ck: Checks):
    from repro.configs import FLConfig, NOMAConfig
    from repro.fl.rounds import run_montecarlo

    ncfg = NOMAConfig(n_subchannels=K)
    # selections are exact in both pairings (admission ranks by age
    # priority); round times: strong_weak is fp32-tight, hungarian reads
    # the kernel's bf16 table (the bf16 tier, DESIGN.md section 13)
    for pairing, rtol in (("strong_weak", 1e-5), ("hungarian", 1e-2)):
        res = {}
        for backend in ("auto", "xla"):
            t0 = time.perf_counter()
            res[backend] = run_montecarlo(ncfg, FLConfig(), pairing=pairing,
                                          kernel_backend=backend, **SWEEP)
            print(f"  {pairing}/{backend}: impl="
                  f"{res[backend]['meta']['kernel_impl']} "
                  f"{time.perf_counter() - t0:.1f} s "
                  f"mean_t_round_s="
                  f"{res[backend]['summary']['age_noma']['mean_t_round_s']}",
                  flush=True)
        ck.true(f"{pairing}: auto ran the compiled kernels",
                res["auto"]["meta"]["kernel_impl"] == "pallas")
        got, want = res["auto"]["age_noma"], res["xla"]["age_noma"]
        for k in ("participation", "final_ages", "n_selected", "max_age"):
            ck.equal(f"{pairing} {k}", got[k], want[k])
        ck.close(f"{pairing} t_round", got["t_round"], want["t_round"],
                 rtol=rtol)


def fl_round(ck: Checks):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.fl.server as server_mod
    from repro.configs import FLConfig, NOMAConfig
    from repro.data import TaskConfig

    cfg = model_config()
    fl = FLConfig(n_clients=20, engine="jax", kernel_backend="auto",
                  predictor="none", samples_per_client=(64, 160), seed=0)
    # the task vocabulary stays small: topic_matrices is 8 V^2 fp64
    task = TaskConfig(vocab_size=512, seq_len=33)
    server = server_mod.FLServer(cfg, fl, NOMAConfig(n_subchannels=K), task,
                                 engine="jax", agg_impl="pallas",
                                 predictor="none")
    n_params = sum(p.size for p in jax.tree.leaves(server.params))
    print(f"  smollm_135m: {n_params} params, cohort {2 * K} of "
          f"{fl.n_clients}", flush=True)
    ck.true("planner runs compiled kernels", server.engine.impl == "pallas")
    ck.true("aggregation runs the fedagg kernel", server.agg_impl == "pallas")

    real = server_mod.aggregate_deltas
    ratios, folds = [], []

    def checked(deltas, weights, *, impl):
        folds.append(len(deltas))
        out = real(deltas, weights, impl=impl)
        twin = real(deltas, weights, impl="xla")
        c = len(deltas)
        for i, (a, b) in enumerate(zip(jax.tree.leaves(out),
                                       jax.tree.leaves(twin))):
            big = max(float(jnp.max(jnp.abs(jax.tree.leaves(d)[i])))
                      for d in deltas)
            # weights are normalized: both sums lie within
            # c * eps * max|delta| of the exact one
            bound = 2 * c * EPS32 * big
            ratios.append(float(jnp.max(jnp.abs(a - b))) / max(bound, 1e-30))
        return out

    server_mod.aggregate_deltas = checked
    before = np.asarray(jax.tree.leaves(server.params)[0])
    t0 = time.perf_counter()
    hist = server.run(3)
    print(f"  3 rounds in {time.perf_counter() - t0:.1f} s; "
          f"loss={hist.loss} round_time={hist.round_time} "
          f"n_selected={hist.n_selected}", flush=True)
    after = np.asarray(jax.tree.leaves(server.params)[0])
    ck.true("3 rounds ran", len(hist.round_time) == 3)
    ck.true("loss is finite", bool(np.all(np.isfinite(hist.loss))))
    ck.true("t_round > 0", all(t > 0 for t in hist.round_time))
    ck.true("parameters changed", bool(np.any(before != after)))
    ck.true("every fold of the running FedAvg was checked",
            len(ratios) == len(folds) * len(jax.tree.leaves(server.params))
            and len(folds) == sum(hist.n_selected))
    ck.true("fedagg aggregate == XLA twin within the fp32 bound",
            bool(ratios) and max(ratios) <= 1.0,
            f"max err/bound={max(ratios, default=float('nan')):.3e}")


def sharded_sweep(ck: Checks):
    import jax
    import numpy as np

    from repro.configs import FLConfig, NOMAConfig
    from repro.core.engine import WirelessEngine
    from repro.fl.rounds import run_montecarlo
    from repro.sim import as_scenario

    ck.true("four devices", len(jax.devices()) == 4, str(jax.devices()))
    ncfg = NOMAConfig(n_subchannels=K)
    kw = dict(SWEEP, pairing="hungarian", kernel_backend="auto")
    runs = {}
    for shard in (False, True):
        t0 = time.perf_counter()
        runs[shard] = run_montecarlo(ncfg, FLConfig(), shard=shard, **kw)
        print(f"  shard={shard}: {time.perf_counter() - t0:.1f} s", flush=True)
    ck.true("auto ran the compiled kernels",
            runs[True]["meta"]["kernel_impl"] == "pallas")
    got, want = runs[True]["age_noma"], runs[False]["age_noma"]
    for k in ("participation", "final_ages", "n_selected", "max_age"):
        ck.equal(k, got[k], want[k])
    ck.close("t_round", got["t_round"], want["t_round"], rtol=1e-6)
    eng = WirelessEngine(ncfg, FLConfig(), kernel_backend="auto",
                         pairing="hungarian")
    out = eng.montecarlo_scenario(
        as_scenario(SWEEP["scenario"], ncfg, FLConfig()),
        rounds=SWEEP["rounds"], n_seeds=SWEEP["n_seeds"],
        n_clients=SWEEP["n_clients"], model_bits=1e6, shard=True)
    devs = out["t_round"].sharding.device_set
    ck.true("outputs span 4 devices", len(devs) == 4, str(sorted(
        d.id for d in devs)))
    ck.equal("direct sharded call == run_montecarlo",
             np.asarray(out["participation"]), got["participation"])


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded sweep, on a 4-chip host")
    args = ap.parse_args()

    from repro.launch import compile_cache
    cache = compile_cache.enable()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found: JAX's first device is "
                 f"{dev.platform!r} ({dev.device_kind})")
    print(f"device: {dev.device_kind} x{len(jax.devices())}, jax "
          f"{jax.__version__}, compile cache {cache}", flush=True)
    phases = ([("sharded_sweep", sharded_sweep)] if args.four_chips else
              [("kernels", kernels), ("sweep", sweep), ("fl", fl_round)])
    for name, fn in phases:
        with phase(name, dev) as ck:
            fn(ck)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
