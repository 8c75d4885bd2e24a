import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: .lower().compile() every (architecture x input shape x
mesh) combination on 512 placeholder host devices, dump memory/cost/
collective analysis for EXPERIMENTS.md sections Dry-run and Roofline.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm_135m \
        --shape train_4k [--multipod] [--out experiments/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, ShapeConfig, get_config
from repro.configs.base import ModelConfig
from repro.launch import roofline as RL
from repro.launch.mesh import make_production_mesh, mesh_info
from repro.models import zoo


# ---------------------------------------------------------------------------
# abstract inputs
# ---------------------------------------------------------------------------


def abstract_params_and_specs(cfg: ModelConfig):
    """Abstract params via eval_shape; the (static) logical spec tree is
    captured from the same trace."""
    captured = {}

    def build():
        p, s = zoo.init_model(jax.random.PRNGKey(0), cfg)
        captured["specs"] = s
        return p

    params = jax.eval_shape(build)
    return params, captured["specs"]


def decode_cache_len(cfg: ModelConfig, shape: ShapeConfig) -> int:
    """long_500k uses the sliding-window ring cache for attention archs
    (the sub-quadratic carve-in, DESIGN.md section 5)."""
    if shape.name == "long_500k" and cfg.family != "ssm":
        return cfg.long_context_window
    return shape.seq_len


def input_specs(cfg: ModelConfig, shape: ShapeConfig, minfo):
    """ShapeDtypeStruct stand-ins + NamedShardings for every model input."""
    shapes = zoo.batch_shapes(cfg, shape)
    specs = zoo.batch_specs(cfg, shape, minfo)
    return shapes, specs


# ---------------------------------------------------------------------------
# analytic flop helpers (scan-trip correction + MODEL_FLOPS)
# ---------------------------------------------------------------------------


def head_flops_per_microbatch_device(cfg, shape, minfo, micro, train):
    tokens = shape.global_batch * shape.seq_len
    tok_dev = tokens / minfo.batch_size_total / micro
    vsh = minfo.model_size if cfg.vocab_size % minfo.model_size == 0 else 1
    f = 2.0 * tok_dev * cfg.d_model * cfg.vocab_size / vsh
    return f * (3.0 if train else 1.0)


def outer_flops_train(cfg, params, minfo):
    # parameter update ~3 flops/param, params sharded across everything when
    # fsdp; conservatively assume model-axis sharding only
    n = sum(int(jnp.prod(jnp.array(p.shape))) for p in jax.tree.leaves(params))
    return 3.0 * n / minfo.model_size


def analytic_model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Global 'useful' flops per step: 6*N_active*T (train) / 2*N_active*T
    (prefill) / 2*N_active*B (decode) + attention term."""
    n_active = cfg.active_param_count()
    b, s = shape.global_batch, shape.seq_len
    h, hd = cfg.n_heads, cfg.head_dim
    if shape.kind == "train":
        base = 6.0 * n_active * b * s
        attn = 12.0 * b * s * s * h * hd * cfg.n_layers * 0.5
    elif shape.kind == "prefill":
        base = 2.0 * n_active * b * s
        attn = 4.0 * b * s * s * h * hd * cfg.n_layers * 0.5
    else:  # decode: one token, attention over the (possibly windowed) cache
        base = 2.0 * n_active * b
        ctx = min(s, cfg.long_context_window) if s > 40_000 else s
        attn = 4.0 * b * ctx * h * hd * cfg.n_layers
    if cfg.family == "ssm":
        attn = 0.0
    return base + attn


# ---------------------------------------------------------------------------
# one (arch, shape, mesh) dry-run
# ---------------------------------------------------------------------------


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               verbose: bool = True, variant: dict | None = None) -> dict:
    """``variant``: optional §Perf-lever overrides, e.g.
    {"accum_dtype": "bfloat16", "act_model_shard": True, "micro": 8,
     "note": "tag"}."""
    variant = variant or {}
    cfg = get_config(arch)
    if "long_context_window" in variant:
        cfg = dataclasses.replace(
            cfg, long_context_window=variant["long_context_window"])
    shape = SHAPES[shape_name]
    policy = zoo.policy_for(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    minfo = mesh_info(mesh)
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    chips = minfo.batch_size_total * minfo.model_size

    params_abs, spec_tree = abstract_params_and_specs(cfg)
    pspecs = zoo.specs_with_dims(params_abs, spec_tree, cfg, minfo, policy)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)

    t0 = time.time()
    micro = 1
    if shape.kind == "train":
        micro = zoo.effective_microbatches(
            shape.global_batch,
            variant.get("micro", policy.micro_for(shape.name)),
            minfo.batch_size_total)
        bax = minfo.batch_axes if len(minfo.batch_axes) > 1 \
            else minfo.batch_axes[0]
        step = zoo.make_train_step(
            cfg, lr=1e-3, microbatches=micro,
            param_pspecs=pspecs, batch_dim_spec=bax,
            accum_dtype=jnp.dtype(variant.get("accum_dtype", "float32")),
            act_model_shard=variant.get("act_model_shard", False))
        bshapes, bspecs = input_specs(cfg, shape, minfo)
        bshard = jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs)
        metric_shard = jax.tree.map(
            lambda _: NamedSharding(mesh, P()), {"loss": 0, "grad_norm": 0})
        with mesh:
            lowered = jax.jit(step, in_shardings=(pshard, bshard),
                              out_shardings=(pshard, metric_shard),
                              donate_argnums=(0,)
                              ).lower(params_abs, bshapes)
        scan_trips = cfg.n_layers * micro
        outer = outer_flops_train(cfg, params_abs, minfo)
        head = head_flops_per_microbatch_device(cfg, shape, minfo, micro,
                                                True)
    elif shape.kind == "prefill":
        bax = minfo.batch_axes if len(minfo.batch_axes) > 1 \
            else minfo.batch_axes[0]
        ring = (mesh, bax, "model") if variant.get("ring_attn") else None
        step = zoo.make_prefill_step(cfg, ring=ring)
        bshapes, bspecs = input_specs(cfg, shape, minfo)
        bshard = jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs)
        with mesh:
            lowered = jax.jit(step, in_shardings=(pshard, bshard)
                              ).lower(params_abs, bshapes)
        scan_trips = max(cfg.n_layers, cfg.n_enc_layers)
        outer = 0.0
        head = head_flops_per_microbatch_device(cfg, shape, minfo, 1, False) \
            / shape.seq_len  # last-token-only unembed
    else:  # decode
        ring = (shape.name == "long_500k" and cfg.family != "ssm")
        cache_len = decode_cache_len(cfg, shape)
        step = zoo.make_serve_step(cfg, ring=ring)
        cache_abs = jax.eval_shape(
            lambda: zoo.init_cache(cfg, shape.global_batch, cache_len))
        cspecs = zoo.specs_with_dims(cache_abs, zoo.cache_specs(cfg), cfg,
                                     minfo, policy)
        cshard = jax.tree.map(lambda s: NamedSharding(mesh, s), cspecs)
        b = shape.global_batch
        bax = minfo.batch_axes if len(minfo.batch_axes) > 1 \
            else minfo.batch_axes[0]
        tok_spec = P(bax) if b % minfo.batch_size_total == 0 else P()
        token = jax.ShapeDtypeStruct((b,), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        with mesh:
            lowered = jax.jit(
                step,
                in_shardings=(pshard, cshard, NamedSharding(mesh, tok_spec),
                              NamedSharding(mesh, P())),
                donate_argnums=(1,),
            ).lower(params_abs, cache_abs, token, pos)
        scan_trips = cfg.n_layers
        outer = 0.0
        vsh = minfo.model_size if cfg.vocab_size % minfo.model_size == 0 else 1
        head = 2.0 * (b / max(1, minfo.batch_size_total if
                              b % minfo.batch_size_total == 0 else 1)) \
            * cfg.d_model * cfg.vocab_size / vsh

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()

    rf = RL.build_roofline(
        arch=arch, shape=shape_name, mesh_name=mesh_name, chips=chips,
        device_kind=RL.TARGET_KIND, cost=cost, mem=mem, hlo=hlo,
        scan_trips=scan_trips,
        outer_flops_per_dev=outer + head,  # head counted once in raw
        model_flops=analytic_model_flops(cfg, shape),
        note=variant.get("note", ""))
    # head is INSIDE the scans for train; adjust: corrected by build_roofline
    # treats (outer+head) as unscanned — for train the head repeats per
    # microbatch, a second-order effect folded into the note.
    record = dataclasses.asdict(rf)
    record.update({
        "micro": micro, "scan_trips": scan_trips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "argument_bytes_per_dev": mem.argument_size_in_bytes,
        "temp_bytes_per_dev": mem.temp_size_in_bytes,
        "output_bytes_per_dev": mem.output_size_in_bytes,
        "alias_bytes_per_dev": mem.alias_size_in_bytes,
        "collectives": RL.collective_stats(hlo).by_kind,
        "ok": True,
    })
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: "
              f"args={mem.argument_size_in_bytes/2**30:.2f}GiB "
              f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB "
              f"flops/dev={rf.hlo_flops:.3e} coll={rf.collective_bytes:.3e}B "
              f"bottleneck={rf.bottleneck} "
              f"(lower {t_lower:.1f}s compile {t_compile:.1f}s)")
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + ["all"])
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + ["all"])
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="all archs x shapes x both meshes")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = ARCH_IDS if (args.all or args.arch in (None, "all")) \
        else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape in (None, "all")) \
        else [args.shape]
    meshes = [False, True] if (args.all or args.both_meshes) \
        else [args.multipod]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
                try:
                    rec = dryrun_one(arch, shape, multi_pod=mp)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    rec = {"ok": False, "error": str(e)[-2000:], "arch": arch,
                           "shape": shape,
                           "mesh": "multi" if mp else "single"}
                    failures.append(tag)
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=1, allow_nan=False)
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)
    print("dry-run: all combinations lowered + compiled")


if __name__ == "__main__":
    main()
