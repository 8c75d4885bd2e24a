"""Driver of ``fl.FLServer.run_round`` for a mixture-of-experts model with
latent attention (Moonlight-16B-A3B) on one chip's share of an
expert-parallel deployment: the fl driver's closed loop of federated
rounds, records and comparison (``bench/drivers/fl.py``), with the plain
reference ``bench/reference/moonlight.py`` in the llama reference's place.

Set-up builds the program's ``ModelConfig`` first, before any data or
weights: a program without the latent-attention, shared-expert, routing or
held-expert fields stops there. Each round's record adds the routing
counters the program's steps returned (``moe_routed``, the (token, choice)
pairs routed to held experts; ``moe_rows``, the rows given to the expert
products), which ``LocalTrainer`` sums per client. The numbers add
``routing_mismatch``: the share of (token, choice) selections of the first
recorded step (the seed's weights, the first client's first batch) that
differ between the program's forward and the reference, over every MoE
layer. It is reported beside the cell's limits, not judged: a
default-precision forward flips near ties on about 1 % of the choices,
and the bfloat16 control reads less than twice that.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from bench.drivers import fl as base
from bench.reference import moonlight

COUNTERS = ("moe_routed", "moe_rows")


@contextlib.contextmanager
def _reference():
    """The fl driver's methods with this model's reference."""
    llama = base.llama
    base.llama = moonlight
    try:
        yield
    finally:
        base.llama = llama


class Driver(base.Driver):
    def __init__(self, ctx):
        super().__init__(ctx)
        self.counts = dict.fromkeys(COUNTERS, 0)

    def _model_cfg(self):
        return self.model_cfg

    def _build_model_cfg(self):
        from repro.configs import ModelConfig
        c, ep = self.c, self.c["expert_parallel"]
        if c["scoring_func"] != "sigmoid" or c["topk_method"] != "noaux_tc" \
                or (c["n_group"], c["topk_group"]) != (1, 1) \
                or c["q_lora_rank"] is not None or not c["norm_topk_prob"]:
            raise SystemExit("bench: fl_moe drives sigmoid noaux_tc routing "
                             "in one group, normalised, and q_lora_rank null")
        try:
            return ModelConfig(
                name=c["name"], family="moe", n_layers=c["num_hidden_layers"],
                d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
                n_kv_heads=c["num_key_value_heads"],
                d_ff=c["moe_intermediate_size"], vocab_size=c["vocab_size"],
                n_experts=ep["router_experts"],
                top_k=c["num_experts_per_tok"], router="sigmoid",
                routed_scale=c["routed_scaling_factor"],
                experts_held=c["n_routed_experts"],
                first_held_expert=ep["first_held_expert"],
                n_shared_experts=c["n_shared_experts"],
                first_dense_layers=c["first_k_dense_replace"],
                dense_d_ff=c["intermediate_size"],
                kv_lora_rank=c["kv_lora_rank"],
                qk_nope_head_dim=c["qk_nope_head_dim"],
                qk_rope_head_dim=c["qk_rope_head_dim"],
                v_head_dim=c["v_head_dim"],
                tie_embeddings=c["tie_word_embeddings"], glu=True,
                norm_eps=c["rms_norm_eps"], rope_theta=float(c["rope_theta"]),
                dtype=c["train_dtype"])
        except TypeError as e:
            raise SystemExit(f"bench: the program's ModelConfig cannot state "
                             f"this model: {e}") from None

    # -- set-up -----------------------------------------------------------
    def setup(self):
        self.model_cfg = self._build_model_cfg()
        with _reference():
            super().setup()

    def _hook(self, server_mod):
        super()._hook(server_mod)
        trainer = self.server.trainer
        inner = trainer.local_update

        def local_update(params, batches):
            out = inner(params, batches)
            for k in COUNTERS:
                self.counts[k] += trainer.counters.get(k, 0)
            return out

        trainer.local_update = local_update

    # -- window -----------------------------------------------------------
    def run_unit(self) -> dict:
        self.counts = dict.fromkeys(COUNTERS, 0)
        rec = super().run_unit()
        rec.update(self.counts)
        return rec

    def close(self):
        with _reference():
            super().close()

    # -- comparison -------------------------------------------------------
    def _first_batch(self):
        first = next(r for r in self.rounds if "clients" in r)
        return jnp.asarray(first["clients"][0]["batches"][0])

    def chosen(self, dtype=None) -> np.ndarray:
        """Each MoE layer's chosen experts on the first recorded batch at
        the seed's weights, (layers, tokens, k): the program's forward, or
        with ``dtype`` the reference's in that precision."""
        from repro.models import zoo
        tokens = self._first_batch()
        p0 = moonlight.init_params(self._weight_key(), self.c,
                                   dtype or jnp.float32)
        if dtype is not None:
            return np.asarray(moonlight.chosen_experts(
                p0, tokens, cfg=moonlight._cfg(self.c)))
        cfg = self.model_cfg
        experts = jax.jit(lambda p, t: zoo.forward(
            cfg, p, {"tokens": t[:, :-1]}, remat=False,
            stats=True)[2]["experts"])(p0, tokens)
        return np.asarray(experts)

    @staticmethod
    def mismatch(got: np.ndarray, ref: np.ndarray) -> float:
        """Share of (token, choice) selections of ``ref`` that ``got``
        does not make, whatever their order."""
        if got.shape != ref.shape:
            return float("inf")
        hit = (got[..., :, None] == ref[..., None, :]).any(-1)
        return float(1.0 - hit.mean())

    def numbers(self, dtype=None) -> dict:
        with _reference():
            out = super().numbers(dtype)
        out["routing_mismatch"] = self.mismatch(
            self.chosen(dtype), self.chosen(jnp.float32))
        return out
