"""Integration + property tests for the FL runtime (server, aggregation,
data pipeline, checkpointing)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro import checkpoint as ckpt
from repro.configs import FLConfig, NOMAConfig, get_config
from repro.data import (
    TaskConfig,
    balanced_eval_set,
    bayes_optimal_accuracy,
    partition_clients,
    topic_matrices,
)
from repro.fl import FLServer, aggregate_deltas, apply_aggregate
from repro.models import zoo

TINY = dataclasses.replace(get_config("smollm_135m").reduced(),
                           d_model=32, d_ff=64, vocab_size=32, n_layers=2)
TASK = TaskConfig(vocab_size=32, n_topics=4, seq_len=17, seed=0)
FL = FLConfig(n_clients=8, rounds=3, local_epochs=1, local_batch=8,
              lr=0.2, samples_per_client=(24, 48), seed=0)
NCFG = NOMAConfig(n_subchannels=2)


class TestData:
    def test_partition_deterministic(self):
        a = partition_clients(FL, TASK)
        b = partition_clients(FL, TASK)
        for ca, cb in zip(a, b):
            np.testing.assert_array_equal(ca.sequences, cb.sequences)

    def test_partition_sizes_and_range(self):
        clients = partition_clients(FL, TASK)
        assert len(clients) == FL.n_clients
        for c in clients:
            assert FL.samples_per_client[0] <= c.n_samples \
                <= FL.samples_per_client[1]
            assert c.sequences.min() >= 0
            assert c.sequences.max() < TASK.vocab_size
            assert c.topic_mix.shape == (TASK.n_topics,)
            assert c.topic_mix.sum() == pytest.approx(1.0)

    def test_topics_are_distinct_chains(self):
        mats = topic_matrices(TASK)
        assert mats.shape == (4, 32, 32)
        np.testing.assert_allclose(mats.sum(-1), 1.0, rtol=1e-9)
        assert np.abs(mats[0] - mats[1]).max() > 0.1

    def test_bayes_ceiling_beats_chance(self):
        assert bayes_optimal_accuracy(TASK) > 2.0 / TASK.vocab_size

    def test_eval_set_balanced(self):
        ev = balanced_eval_set(TASK, n_per_topic=8)
        assert ev.shape == (32, 17)


class TestAggregate:
    @given(st.integers(1, 6), st.integers(0, 1000))
    @settings(max_examples=20, deadline=None)
    def test_weighted_sum_linearity(self, c, seed):
        """FedAvg aggregation == manual weighted sum over pytrees."""
        key = jax.random.PRNGKey(seed)
        deltas = [
            {"a": jax.random.normal(jax.random.fold_in(key, i), (5, 3)),
             "b": jax.random.normal(jax.random.fold_in(key, 100 + i), (7,))}
            for i in range(c)]
        w = np.random.default_rng(seed).uniform(0.1, 1.0, c)
        agg = aggregate_deltas(deltas, w)
        wn = w / w.sum()
        expect_a = sum(wn[i] * deltas[i]["a"] for i in range(c))
        np.testing.assert_allclose(np.asarray(agg["a"]),
                                   np.asarray(expect_a), rtol=1e-5,
                                   atol=1e-5)

    def test_identity_aggregation(self):
        """Single client with weight 1 -> exact delta."""
        d = {"w": jnp.arange(12.0).reshape(3, 4)}
        agg = aggregate_deltas([d], np.array([5.0]))
        np.testing.assert_allclose(np.asarray(agg["w"]), np.asarray(d["w"]))

    # leaves of rank 0-4, minor dims off 128 lanes, second-minor off 8
    LEAVES = {"r0": (), "r1": (7,), "r2": (5, 130), "r3": (3, 9, 129),
              "r4": (2, 3, 17, 200)}

    def _cohort(self, c, dtype, seed=0):
        key = jax.random.PRNGKey(seed)
        deltas = [{k: jax.random.normal(jax.random.fold_in(key, 8 * i + j),
                                        s, jnp.float32).astype(dtype)
                   for j, (k, s) in enumerate(self.LEAVES.items())}
                  for i in range(c)]
        w = np.random.default_rng(seed + c).uniform(0.1, 1.0, c)
        # the weights as the program normalises them, in fp32
        w32 = jnp.asarray(w, jnp.float32)
        return deltas, w, w32 / jnp.maximum(jnp.sum(w32), 1e-9)

    @staticmethod
    def _within_summation_bound(out, expect, deltas, wn, name):
        """|out - expect| <= c * eps * sum_c |w_c u_c| (fp64), elementwise:
        the fp32 summation bound of a C-term weighted sum, doubled so that
        two fp32 sums (each within c * eps / 2) may differ by it."""
        u = np.stack([np.asarray(d[name], np.float64) for d in deltas])
        w64 = np.asarray(wn, np.float64)
        bound = (len(deltas) * np.finfo(np.float32).eps
                 * np.tensordot(np.abs(w64), np.abs(u), 1))
        gap = np.abs(np.asarray(out, np.float64)
                     - np.asarray(expect, np.float64))
        assert np.all(gap <= bound), name

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("c", [1, 10, 50])
    def test_fused_matches_stacked_reference(self, c, dtype):
        """The fused sum (default impl) against ``weighted_sum_ref`` on
        stacked copies and the fp64 sum, leaf by leaf: fp32 leaves of the
        deltas' own shapes, within the fp32 summation bound."""
        from repro.kernels import ref
        deltas, w, wn = self._cohort(c, dtype)
        agg = aggregate_deltas(deltas, w)
        for name, shape in self.LEAVES.items():
            out = agg[name]
            assert out.shape == shape and out.dtype == jnp.float32
            stacked = jnp.stack([d[name] for d in deltas]).reshape(c, -1)
            expect = ref.weighted_sum_ref(stacked, wn).reshape(shape)
            self._within_summation_bound(out, expect, deltas, wn, name)
            exact = np.tensordot(
                np.asarray(wn, np.float64),
                np.stack([np.asarray(d[name], np.float64) for d in deltas]),
                1)
            self._within_summation_bound(out, exact, deltas, wn, name)

    @pytest.mark.parametrize("c", [1, 10, 50])
    def test_fused_matches_kernel_path(self, c):
        """The fused sum against the stacked ``fedagg`` kernel path
        (interpret) within the fp32 summation bound."""
        deltas, w, wn = self._cohort(c, jnp.float32, seed=1)
        fused = aggregate_deltas(deltas, w, impl="xla")
        kernel = aggregate_deltas(deltas, w, impl="interpret")
        for name in self.LEAVES:
            self._within_summation_bound(fused[name], kernel[name], deltas,
                                         wn, name)

    @pytest.mark.parametrize("impl", ["xla", "interpret"])
    def test_blend_without_predictions_is_aggregate(self, impl):
        from repro.fl import blend_deltas
        deltas, w, _ = self._cohort(4, jnp.bfloat16, seed=2)
        a = aggregate_deltas(deltas, w, impl=impl)
        b = blend_deltas(deltas, w, [], np.zeros((0,)), impl=impl)
        for name in self.LEAVES:
            np.testing.assert_array_equal(np.asarray(a[name]),
                                          np.asarray(b[name]))

    def test_fused_path_stacks_nothing(self):
        """The default path's program holds no stack, flattening, pad or
        slice update of the deltas."""
        import re

        from repro.fl import aggregate
        deltas, w, _ = self._cohort(10, jnp.float32)
        text = str(jax.make_jaxpr(aggregate._fused_sum)(
            deltas, jnp.asarray(w, jnp.float32)))
        assert not re.search(
            r"\b(concatenate|reshape|pad|dynamic_update_slice)\[", text)

    def test_apply_aggregate_moves_params(self):
        p = {"w": jnp.zeros((4,), jnp.float32)}
        d = {"w": jnp.ones((4,), jnp.float32)}
        out = apply_aggregate(p, d, server_lr=0.5)
        np.testing.assert_allclose(np.asarray(out["w"]), 0.5)


class TestServer:
    @pytest.mark.slow
    def test_three_rounds_run_and_learn_signal(self):
        srv = FLServer(TINY, FL, NCFG, TASK, policy="age_noma", eval_every=1)
        hist = srv.run(3)
        assert len(hist.rounds) == 3
        assert all(np.isfinite(hist.loss))
        assert all(t > 0 for t in hist.round_time)
        assert srv.t_sim == pytest.approx(sum(hist.round_time))
        # ages: selected reset, others grew
        assert srv.ages.max() >= 1

    @pytest.mark.slow
    def test_policies_all_run(self):
        for policy in ("age_noma", "age_noma_budget", "random", "channel",
                       "round_robin", "oma_age"):
            srv = FLServer(TINY, FL, NCFG, TASK, policy=policy,
                           eval_every=10)
            hist = srv.run(2)
            assert len(hist.rounds) == 2, policy
            assert hist.participation.sum() > 0

    def test_same_seed_same_topology(self):
        s1 = FLServer(TINY, FL, NCFG, TASK, policy="age_noma")
        s2 = FLServer(TINY, FL, NCFG, TASK, policy="channel")
        np.testing.assert_allclose(s1.distances, s2.distances)
        np.testing.assert_allclose(s1.n_samples, s2.n_samples)

    @pytest.mark.slow
    def test_jax_engine_matches_numpy_selection(self):
        """FLConfig.engine='jax' routes scheduling through core/engine.py;
        same seed => same per-round selections and round times as the
        numpy reference scheduler."""
        s_np = FLServer(TINY, FL, NCFG, TASK, policy="age_noma",
                        eval_every=10)
        s_jx = FLServer(TINY, FL, NCFG, TASK, policy="age_noma",
                        eval_every=10, engine="jax")
        assert s_jx.engine is not None
        for _ in range(2):
            a = s_np.run_round()
            b = s_jx.run_round()
            np.testing.assert_array_equal(a.selected, b.selected)
            assert sorted(a.pairs) == sorted(b.pairs)
            assert b.t_round == pytest.approx(a.t_round, rel=1e-4)
            assert b.info["engine"] == "jax"


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        params, _ = zoo.init_model(jax.random.PRNGKey(0), TINY)
        path = str(tmp_path / "ck")
        ckpt.save(path, params, step=7, extra={"note": "x"})
        assert ckpt.latest_step(path) == 7
        like = jax.tree.map(
            lambda a: jnp.zeros(a.shape, a.dtype), params)
        restored, manifest = ckpt.restore(path, like)
        assert manifest["step"] == 7
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_overwrite_keeps_latest(self, tmp_path):
        tree = {"x": jnp.ones((3,))}
        path = str(tmp_path / "ck")
        ckpt.save(path, tree, step=1)
        ckpt.save(path, {"x": 2 * jnp.ones((3,))}, step=2)
        restored, m = ckpt.restore(path, tree)
        assert m["step"] == 2
        np.testing.assert_allclose(np.asarray(restored["x"]), 2.0)


class TestOptim:
    def test_sgd_momentum(self):
        from repro.optim import SGD
        opt = SGD(lr=0.1, momentum=0.9)
        p = {"w": jnp.ones((2,))}
        st_ = opt.init(p)
        g = {"w": jnp.ones((2,))}
        upd, st_ = opt.update(g, st_, p)
        np.testing.assert_allclose(np.asarray(upd["w"]), -0.1)
        upd, st_ = opt.update(g, st_, p)
        np.testing.assert_allclose(np.asarray(upd["w"]), -0.19)

    def test_adamw_step_and_decay(self):
        from repro.optim import AdamW
        opt = AdamW(lr=1e-2, weight_decay=0.1)
        p = {"w": jnp.ones((2,))}
        s = opt.init(p)
        g = {"w": jnp.full((2,), 0.5)}
        upd, s = opt.update(g, s, p)
        assert s["t"] == 1
        assert np.all(np.asarray(upd["w"]) < 0)

    def test_schedules(self):
        from repro.optim import schedules
        cos = schedules.cosine(100, warmup=10)
        assert cos(0) == 0.0
        assert cos(10) == pytest.approx(1.0)
        assert cos(100) == pytest.approx(0.1, abs=1e-6)
        inv = schedules.inverse_sqrt(10)
        assert inv(10) == pytest.approx(1.0)
        assert inv(40) == pytest.approx(0.5)
