"""Kernel micro-benchmarks: XLA twin vs Pallas-interpret oracle timing on
CPU (correctness-weighted; real perf numbers require TPU — documented in
EXPERIMENTS.md) plus derived arithmetic-intensity metadata for the roofline
narrative."""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.kernels import ops


def _time(fn, *args, reps=5, **kw):
    fn(*args, **kw)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def run(*, smoke=False, out_path=None, seed=0):
    # smoke only cuts reps — shapes stay identical to the full run so the
    # regression gate can match rows against committed baselines
    reps = 2 if smoke else 5
    rows = []
    key = jax.random.PRNGKey(seed)

    # fedagg: 10 clients x 1M-param update
    c, n = 10, 1 << 20
    u = jax.random.normal(key, (c, n), jnp.float32)
    w = jax.random.uniform(jax.random.PRNGKey(seed + 1), (c,))
    us_xla = _time(lambda: ops.weighted_sum(u, w, impl="xla"), reps=reps)
    flops = c * n * 2
    bytes_ = (c * n + n) * 4
    rows.append({"kernel": "fedagg", "shape": f"{c}x{n}",
                 "us_xla_cpu": us_xla, "flops": flops, "bytes": bytes_,
                 "arith_intensity": flops / bytes_})

    # wkv6 chunked vs naive recurrence
    b, h, t, cd = 1, 8, 1024, 64
    ks = jax.random.split(key, 5)
    r, k2, v = (jax.random.normal(ks[i], (b, h, t, cd)) * 0.5
                for i in range(3))
    wl = -jnp.exp(jax.random.normal(ks[3], (b, h, t, cd)))
    uu = jax.random.normal(ks[4], (h, cd)) * 0.5
    from repro.models.rwkv import wkv6_chunked
    from repro.kernels.ref import wkv6_ref
    s0 = jnp.zeros((b, h, cd, cd))
    us_chunk = _time(jax.jit(lambda *a: wkv6_chunked(*a, chunk=64)),
                     r, k2, v, wl, uu, s0, reps=reps)
    us_naive = _time(jax.jit(wkv6_ref), r, k2, v, wl, uu, s0, reps=reps)
    rows.append({"kernel": "wkv6", "shape": f"{b}x{h}x{t}x{cd}",
                 "us_chunked_cpu": us_chunk, "us_naive_cpu": us_naive,
                 "chunked_speedup_cpu": us_naive / us_chunk})

    # swa window vs full attention compute ratio
    from repro.kernels.ref import swa_ref
    b, s, hh, kh, hd, win = 1, 2048, 4, 2, 64, 256
    q = jax.random.normal(ks[0], (b, s, hh, hd))
    kk = jax.random.normal(ks[1], (b, s, kh, hd))
    vv = jax.random.normal(ks[2], (b, s, kh, hd))
    us_swa = _time(jax.jit(lambda *a: swa_ref(*a, win)), q, kk, vv,
                   reps=reps)
    rows.append({"kernel": "swa", "shape": f"s{s}w{win}",
                 "us_ref_cpu": us_swa,
                 "flops_vs_full": win / s})

    # NOMA pair scoring + fused round-planner tables (kernels/pairscore.py,
    # kernels/planner.py): xla twin vs pallas-interpret oracle, with
    # analytic flop/byte counts placing each kernel on the TPU roofline
    # (launch/roofline.py kernel_roof_point — shape-derived, not timed;
    # the interpret timings are the CPU correctness path, never gated).
    # The roof is the target chip's, named in every row, whatever device
    # this run is on.
    from repro.launch.roofline import TARGET_KIND, kernel_roof_point
    NOMA_KW = dict(n0b=1e-14, pmax=0.2, bw=1e6)
    PAIR_FLOPS = 25          # sqrt + 2x log1p + div/mul chain per pair

    bq, nq = 64, 256
    gi = jax.random.uniform(ks[0], (bq, nq), minval=1e-8, maxval=1e-5)
    gj = jax.random.uniform(ks[1], (bq, nq), minval=1e-9, maxval=1e-6)
    us_ps_xla = _time(jax.jit(lambda a, b_: ops.pair_alloc_rates(
        a, b_, impl="xla", **NOMA_KW)), gi, gj, reps=reps)
    us_ps_int = _time(lambda: ops.pair_alloc_rates(
        gi, gj, impl="interpret", **NOMA_KW), reps=reps)
    n_el = bq * nq
    flops = n_el * PAIR_FLOPS
    bytes_ = n_el * (2 + 4) * 4          # 2 gain inputs, 4 fp32 outputs
    rp = kernel_roof_point(flops, bytes_, TARGET_KIND)
    rows.append({"kernel": "pairscore", "shape": f"{bq}x{nq}",
                 "us_xla_cpu": us_ps_xla, "us_interpret_cpu": us_ps_int,
                 "flops": flops, "bytes": bytes_,
                 "arith_intensity": rp.intensity, "roof_ridge": rp.ridge,
                 "roof_bound": rp.bound, "roof_device_kind": TARGET_KIND,
                 "roof_peak_fraction": rp.peak_fraction})

    for bp, cp_ in ((8, 10), (4, 256)):
        g = -jnp.sort(-jax.random.uniform(ks[2], (bp, cp_), minval=1e-8,
                                          maxval=1e-5), axis=-1)
        tc = jax.random.uniform(ks[3], (bp, cp_), minval=0.01, maxval=0.2)
        us_pl_xla = _time(lambda: ops.planner_tables(
            g, tc, 1e6, impl="xla", **NOMA_KW), reps=reps)
        us_pl_int = _time(lambda: ops.planner_tables(
            g, tc, 1e6, impl="interpret", **NOMA_KW), reps=reps)
        # c^2 pair-math evals + completion max + row-min/anti-diag reduce
        flops = bp * cp_ * cp_ * (PAIR_FLOPS + 5)
        # fp32 gain/t inputs broadcast from (c,), bf16 table out, fp32
        # row_min out: the fusion's whole point is the O(c) input traffic
        bytes_ = bp * (2 * cp_ * 4 + cp_ * cp_ * 2 + cp_ * 4 + 4)
        rp = kernel_roof_point(flops, bytes_, TARGET_KIND)
        rows.append({"kernel": "planner_tables", "shape": f"{bp}x{cp_}",
                     "us_xla_cpu": us_pl_xla, "us_interpret_cpu": us_pl_int,
                     "flops": flops, "bytes": bytes_,
                     "arith_intensity": rp.intensity,
                     "roof_ridge": rp.ridge, "roof_bound": rp.bound,
                     "roof_device_kind": TARGET_KIND,
                     "roof_peak_fraction": rp.peak_fraction})

    result = {
        "benchmark": "kernels",
        "backend": jax.default_backend(),
        "smoke": smoke,
        "rows": rows,
    }
    out_path = out_path or os.path.join("experiments", "bench",
                                        "BENCH_kernels.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2, allow_nan=False)
    for r_ in rows:
        us = r_.get("us_xla_cpu") or r_.get("us_chunked_cpu") \
            or r_.get("us_ref_cpu")
        print(f"kernel_{r_['kernel']},{r_['shape']},{us:.1f}us")
    print(f"wrote {out_path}")
    return result


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="smaller shapes + fewer reps for CI")
    ap.add_argument("--out", default=None, help="output JSON path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    run(smoke=args.smoke, out_path=args.out, seed=args.seed)


if __name__ == "__main__":
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                           / "src"))
    main()
