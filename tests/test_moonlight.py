"""Moonlight-16B-A3B (latent attention, a leading dense layer, sigmoid-routed
held and shared experts) against the plain reference
``bench/reference/moonlight.py`` at a small size on seeded random weights;
the expert-parallel share; the parameter counts; and the server's running
FedAvg against the one-shot weighted sum."""
from __future__ import annotations

import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import FLConfig, ModelConfig, NOMAConfig, get_config
from repro.models import moe as MOE
from repro.models import zoo

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench.reference import moonlight as ref  # noqa: E402

EPS32 = float(np.finfo(np.float32).eps)


def tiny(held: int = 4, first: int = 4, router: int = 16):
    """(program config, reference config) of one tiny Moonlight: every
    kind of layer, a share of ``held`` of ``router`` routed experts."""
    c = {"hidden_size": 64, "num_attention_heads": 4, "kv_lora_rank": 16,
         "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
         "intermediate_size": 96, "moe_intermediate_size": 32,
         "n_shared_experts": 2, "n_routed_experts": held,
         "num_experts_per_tok": 6, "routed_scaling_factor": 2.446,
         "vocab_size": 96, "first_k_dense_replace": 1,
         "num_hidden_layers": 3, "rms_norm_eps": 1e-5, "rope_theta": 50000,
         # larger than the published 0.02, so that the router's scores are
         # far apart and no top-6 rests on a near tie
         "initializer_range": 0.1,
         "expert_parallel": {"router_experts": router,
                             "first_held_expert": first}}
    cfg = ModelConfig(
        name="moonlight_tiny", family="moe", n_layers=3, d_model=64,
        n_heads=4, n_kv_heads=4, d_ff=32, vocab_size=96, n_experts=router,
        top_k=6, router="sigmoid", routed_scale=2.446, experts_held=held,
        first_held_expert=first, n_shared_experts=2, first_dense_layers=1,
        dense_d_ff=96, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, rope_theta=50000.0,
        dtype="float32")
    return cfg, c


def _batch(seed=1, b=4, s=13):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, s), 0, 96)


def test_program_tree_is_the_reference_layout():
    cfg, c = tiny()
    own, _ = zoo.init_model(jax.random.PRNGKey(0), cfg)
    theirs = ref.init_params(jax.random.PRNGKey(0), c)
    assert jax.tree.structure(own) == jax.tree.structure(theirs)
    assert ([x.shape for x in jax.tree.leaves(own)]
            == [x.shape for x in jax.tree.leaves(theirs)])
    assert sum(x.size for x in jax.tree.leaves(own)) == cfg.param_count()


def _program_loss(cfg, params, tokens):
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    logits, aux = zoo.forward(cfg, params, batch, remat=False)
    return zoo.token_loss(cfg, logits, batch["labels"], aux=aux)


def test_logits_loss_and_every_gradient_leaf_match_the_reference():
    """fp32 on the CPU on both sides: only the order of the sums differs
    (grouped products over sorted rows against dense masked experts,
    fused against unfused), so the logits agree to 1e-5 of their scale,
    the loss to 1e-5, and each gradient leaf to 1e-4 of its own largest
    entry."""
    cfg, c = tiny()
    params = ref.init_params(jax.random.PRNGKey(3), c)
    tokens = _batch()
    want = ref.forward(params, tokens[:, :-1], c)
    got, _ = zoo.forward(cfg, params, {"tokens": tokens[:, :-1]},
                         remat=False)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5 * scale)
    np.testing.assert_allclose(float(_program_loss(cfg, params, tokens)),
                               float(ref.loss(params, tokens, c)), rtol=1e-5)
    g_got = jax.grad(lambda p: _program_loss(cfg, p, tokens))(params)
    g_want = ref.grad(params, tokens, cfg=ref._cfg(c))
    paths = jax.tree_util.tree_flatten_with_path(g_want)[0]
    for (path, w), g in zip(paths, jax.tree.leaves(g_got)):
        name = jax.tree_util.keystr(path)
        top = float(jnp.max(jnp.abs(w)))
        if name.endswith("['bias']"):   # steers the choice only
            assert top == 0.0 and float(jnp.max(jnp.abs(g))) == 0.0
            continue
        assert top > 0, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0,
                                   atol=1e-4 * top, err_msg=name)


def _moe_params(c, seed=5):
    return jax.tree.map(lambda x: x[0], ref.init_params(
        jax.random.PRNGKey(seed), c)["blocks"]["moe"])


def test_router_top6_matches_the_reference():
    """The router's product runs at HIGHEST precision, so the program's
    top-6 equals the reference's for every token (as sets)."""
    cfg, c = tiny()
    p = _moe_params(c)
    x = jax.random.normal(jax.random.PRNGKey(2), (64, 64))
    w, experts, _ = MOE.route(p, x, cfg)
    w_ref, chosen = ref.routing(p, x, ref.dims(c))
    assert np.array_equal(np.sort(np.asarray(experts), -1),
                          np.sort(np.asarray(chosen), -1))
    np.testing.assert_allclose(
        np.asarray(w), np.take_along_axis(np.asarray(w_ref),
                                          np.asarray(experts), -1),
        rtol=1e-6)
    np.testing.assert_allclose(np.asarray(jnp.sum(w, -1)), 2.446, rtol=1e-6)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight shares of 2 of 16 routed experts: their routed parts, with
    the shared experts (which every chip computes alike) counted once, add
    up to the uncut reference layer (all 16 held); fp32 sums in another
    order, so to 1e-5 of the output's scale."""
    cfg_all, c_all = tiny(held=16, first=0)
    p = _moe_params(c_all)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 64))
    want = ref.experts(p, x, ref.dims(c_all))
    shared = ref._swiglu(x, p["shared"])
    total = -7 * shared
    for i in range(8):
        cfg_i = dataclasses.replace(cfg_all, experts_held=2,
                                    first_held_expert=2 * i)
        p_i = dict(p, **{k: p[k][2 * i:2 * i + 2] for k in ("wi", "wg",
                                                           "wo")})
        out, _, stats = MOE.apply_moe(p_i, x, cfg_i)
        total = total + out
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=0,
                               atol=1e-5 * scale)


@pytest.mark.parametrize("held,first", [(4, 4), (16, 0)])
def test_routing_is_dropless_under_a_skewed_router(held, first):
    """Every token prefers the same six experts (four of them held here
    when ``first`` is 4): no pair is dropped, every held expert's load is
    the whole batch, and the layer equals the reference's dense masked
    experts token for token."""
    cfg, c = tiny(held=held, first=first)
    p = _moe_params(c)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 32, 64))
    x = x.at[..., 0].set(1.0)
    p = dict(p, router=p["router"].at[0, 2:8].add(50.0))
    out, _, stats = MOE.apply_moe(p, x, cfg)
    held_chosen = len(set(range(2, 8)) & set(range(first, first + held)))
    assert int(stats["routed"]) == 64 * held_chosen
    assert int(stats["max_load"]) == 64
    assert int(stats["rows"]) == 64 * 6
    want = ref.experts(p, x, ref.dims(c))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=0,
                               atol=1e-5 * float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("reduce", [False, True])
def test_parameter_counts(reduce):
    """The uncut published model, and one chip's share of 8-way expert
    parallelism at depth 5 with a vocabulary slice of 20,480."""
    cfg = get_config("moonshot_v1_16b_a3b")
    if reduce:
        cfg = dataclasses.replace(cfg, n_layers=5, experts_held=8,
                                  vocab_size=20_480)
    want = 568_484_608 if reduce else 15_960_110_208
    assert cfg.param_count() == want
    # top-6 of 64 active per token: 6/64 of the held experts' weights
    mlp = 3 * 2048 * 1408
    n_moe = cfg.n_layers - 1
    assert cfg.active_param_count() == want - round(
        n_moe * mlp * (cfg.n_held - 6 * cfg.n_held / 64))


def test_smollm_param_count_is_the_published_one():
    assert get_config("smollm_135m").param_count() == 134_515_008


# -- the server's running FedAvg -------------------------------------------
def _server(cfg):
    from repro.data import TaskConfig
    from repro.fl import FLServer
    return FLServer(cfg, FLConfig(n_clients=12, local_batch=8, lr=0.2,
                                  samples_per_client=(24, 48), seed=0),
                    NOMAConfig(n_subchannels=5),
                    TaskConfig(vocab_size=32, n_topics=4, seq_len=9, seed=0))


def _arch_cfg(arch):
    """A tiny dense llama ("smollm") or the tiny Moonlight, over a
    32-token vocabulary."""
    if arch == "smollm":
        return dataclasses.replace(get_config("smollm_135m").reduced(),
                                   d_model=32, d_ff=64, vocab_size=32,
                                   n_layers=2)
    return dataclasses.replace(tiny()[0], vocab_size=32)


@pytest.mark.parametrize("arch", ["smollm", "moonlight"])
def test_running_fedavg_is_the_cohort_weighted_sum(arch, monkeypatch):
    """One round of ten clients: the aggregate the server applies, folded
    delta by delta, equals ``aggregate_deltas`` over all ten at once. Each
    fold rounds its two fp32 terms and weights, so the two differ by at
    most 4 C eps sum_c w_c |d_c| elementwise (C folds, each within a few
    eps of its terms)."""
    import repro.fl.server as server_mod
    from repro.fl import aggregate_deltas
    srv = _server(_arch_cfg(arch))
    seen, applied = [], []
    real_local = srv.trainer.local_update
    real_apply = server_mod.apply_aggregate

    def local_update(params, batches):
        delta, loss = real_local(params, batches)
        seen.append(delta)
        return delta, loss

    def apply(params, agg):
        applied.append(agg)
        return real_apply(params, agg)

    srv.trainer.local_update = local_update
    monkeypatch.setattr(server_mod, "apply_aggregate", apply)
    sched = srv.run_round()
    sel = np.flatnonzero(sched.selected)
    assert len(sel) == len(seen) == 10
    w = srv.n_samples[sel]
    once = aggregate_deltas(seen, w)
    wn = w / w.sum()
    for got, want, *ds in zip(jax.tree.leaves(applied[0]),
                              jax.tree.leaves(once),
                              *[jax.tree.leaves(d) for d in seen]):
        bound = 4 * len(sel) * EPS32 * sum(
            wi * np.abs(np.asarray(d, np.float64)) for wi, d in zip(wn, ds))
        assert np.all(np.abs(np.asarray(got, np.float64)
                             - np.asarray(want, np.float64)) <= bound)


def _undefined_past_groups(monkeypatch):
    """Replace ``jax.lax.ragged_dot`` by one that leaves NaN in the rows
    past the last group of its result and of its input gradient, as a
    grouped-product kernel may (the TPU's does not define them)."""
    real = jax.lax.ragged_dot

    def poison(a, sizes):
        return jnp.where((jnp.arange(a.shape[0]) < jnp.sum(sizes))[:, None],
                         a, jnp.nan)

    @jax.custom_vjp
    def ragged_dot(lhs, rhs, group_sizes):
        return poison(real(lhs, rhs, group_sizes), group_sizes)

    def fwd(lhs, rhs, group_sizes):
        return ragged_dot(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        _, vjp = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)
        d_lhs, d_rhs = vjp(g)
        return poison(d_lhs, sizes), d_rhs, None

    ragged_dot.defvjp(fwd, bwd)
    monkeypatch.setattr(jax.lax, "ragged_dot", ragged_dot)


def test_rows_held_elsewhere_never_reach_a_result(monkeypatch):
    """With a grouped product that leaves garbage (NaN) past its groups,
    the loss and every gradient leaf stay finite and equal the
    reference's (tolerances as in the test above)."""
    cfg, c = tiny()
    params = ref.init_params(jax.random.PRNGKey(3), c)
    tokens = _batch()
    _undefined_past_groups(monkeypatch)
    loss, g_got = jax.value_and_grad(
        lambda p: _program_loss(cfg, p, tokens))(params)
    np.testing.assert_allclose(float(loss), float(ref.loss(params, tokens, c)),
                               rtol=1e-5)
    g_want = ref.grad(params, tokens, cfg=ref._cfg(c))
    for g, w in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        assert bool(jnp.all(jnp.isfinite(g)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0,
                                   atol=1e-4 * max(float(jnp.max(jnp.abs(w))),
                                                   1e-30))


# -- the client's local epoch ------------------------------------------------
def _trainer_and_batches(arch, steps=5):
    from repro.fl.client import LocalTrainer
    cfg = _arch_cfg(arch)
    params, _ = zoo.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, 32, (8, 9), dtype=np.int32)
               for _ in range(steps)]
    return LocalTrainer(cfg, lr=0.2), params, batches


def _per_step_update(trainer, params, batches):
    """The local epoch as it was written before: each step's metrics
    pulled to the host right after the step."""
    p, opt_state = params, trainer.opt.init(params)
    losses, counters = [], {}
    for tokens in batches:
        p, opt_state, metrics = trainer.step(p, opt_state,
                                             jnp.asarray(tokens))
        metrics = jax.device_get(metrics)
        losses.append(float(metrics.pop("loss")))
        for k, v in metrics.items():
            counters[k] = counters.get(k, 0) + int(v)
    delta = jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        p, params)
    return delta, float(np.mean(losses)), counters


@pytest.mark.parametrize("arch", ["smollm", "moonlight"])
def test_local_update_equals_the_per_step_loop(arch):
    """From the same batches, ``local_update`` gives the per-step loop's
    mean loss, counters (Python ints) and delta, bit for bit."""
    trainer, params, batches = _trainer_and_batches(arch)
    want_delta, want_loss, want_counters = _per_step_update(
        trainer, params, batches)
    delta, loss = trainer.local_update(params, batches)
    assert loss == want_loss
    assert trainer.counters == want_counters
    assert all(type(v) is int for v in trainer.counters.values())
    assert bool(trainer.counters) == (arch == "moonlight")
    for got, want in zip(jax.tree.leaves(delta), jax.tree.leaves(want_delta)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class _Watched:
    """A step's metric that logs any host conversion of itself, and each
    wait for it (with the index of its step)."""

    def __init__(self, value, log, index):
        self.value, self.log, self.index = value, log, index

    def block_until_ready(self):
        self.log.append(("wait", self.index))
        self.value.block_until_ready()
        return self

    def __array__(self, dtype=None, copy=None):
        self.log.append("read")
        return np.asarray(self.value, dtype)

    def __float__(self):
        self.log.append("read")
        return float(self.value)

    def __int__(self):
        self.log.append("read")
        return int(self.value)


@pytest.mark.parametrize("in_flight", [2, 1])
@pytest.mark.parametrize("arch", ["smollm", "moonlight"])
def test_local_update_pulls_once_after_the_last_step(arch, in_flight,
                                                     monkeypatch):
    """A client of 5 steps: every step is dispatched before the one
    device-to-host transfer, no step's metric is read on the host other
    than through it, and before step i the host has waited for step
    i - ``in_flight``: 2 for weights within ``RUN_AHEAD_BYTES``, else 1."""
    import repro.fl.client as client_mod
    if in_flight == 1:
        monkeypatch.setattr(client_mod, "RUN_AHEAD_BYTES", 0)
    trainer, params, batches = _trainer_and_batches(arch)
    log = []
    real_step, real_get = trainer.step, jax.device_get

    def step(p, opt_state, tokens):
        i = log.count("step")
        log.append("step")
        p, opt_state, metrics = real_step(p, opt_state, tokens)
        return p, opt_state, {k: _Watched(v, log, i)
                              for k, v in metrics.items()}

    def device_get(x):
        log.append("get")
        return real_get(jax.tree.map(
            lambda v: v.value if isinstance(v, _Watched) else v, x,
            is_leaf=lambda v: isinstance(v, _Watched)))

    trainer.step = step
    monkeypatch.setattr(jax, "device_get", device_get)
    trainer.local_update(params, batches)
    waits = [e for e in log if isinstance(e, tuple)]
    assert waits == [("wait", j)
                     for j in range(len(batches) - in_flight)]
    assert [e for e in log if not isinstance(e, tuple)] == (
        ["step"] * len(batches) + ["get"])
    for w in waits:
        assert log[:log.index(w)].count("step") == w[1] + in_flight


@pytest.mark.parametrize("arch", ["smollm", "moonlight"])
def test_every_client_update_notes_one_pull(arch):
    from repro.obs import trace
    srv = _server(_arch_cfg(arch))
    with trace.tracing() as tr:
        srv.run_round()
    spans = [s for s in tr.spans if s.name == "client.update"]
    assert len(spans) == 10
    assert all(s.meta["steps"] >= 3 and s.meta["pulls"] == 1 for s in spans)
    assert all(("moe_routed" in s.meta) == (arch == "moonlight")
               for s in spans)
