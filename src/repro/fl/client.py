"""Client-side local training: a jit'd SGD step reused across all clients
(same pytree structure), driven by the host round loop."""
from __future__ import annotations

from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import zoo
from repro.obs import trace
from repro.optim import SGD, apply_updates


def make_sgd_batch_step(cfg: ModelConfig, lr: float, momentum: float = 0.0):
    """(optimizer, step): ``step(params, opt_state, tokens) -> (params',
    opt_state', metrics)``; ``metrics`` holds the ``loss`` and, for a MoE
    model, the step's routing counters (summed over its MoE layers:
    ``moe_routed`` pairs routed to the held experts, ``moe_rows`` rows
    given to the expert products; ``moe_max_load``, the largest held
    expert's load in any layer)."""
    opt = SGD(lr=lr, momentum=momentum)

    @jax.jit
    def step(params, opt_state, tokens):
        batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}

        def loss_fn(p):
            logits, aux, stats = zoo.forward(cfg, p, batch, remat=False,
                                             stats=True)
            return (zoo.token_loss(cfg, logits, batch["labels"], aux=aux),
                    stats)

        (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params)
        upd, opt_state = opt.update(grads, opt_state, params)
        metrics = {"loss": loss}
        if cfg.is_moe:
            metrics.update(moe_routed=jnp.sum(stats["routed"]),
                           moe_max_load=jnp.max(stats["max_load"]),
                           moe_rows=jnp.sum(stats["rows"]))
        return apply_updates(params, upd), opt_state, metrics

    return opt, step


class LocalTrainer:
    """Runs E local epochs of SGD for one client, returns the model DELTA
    (the uplink payload in the real system)."""

    def __init__(self, cfg: ModelConfig, lr: float, momentum: float = 0.0):
        self.cfg = cfg
        self.opt, self.step = make_sgd_batch_step(cfg, lr, momentum)
        self.counters = {}

    def local_update(self, params, batches: Iterable[np.ndarray]):
        """-> (fp32 delta, mean loss). The step's counters other than the
        loss come to the host with it, one transfer a step; their sums
        over the steps are ``self.counters`` and notes of the open span."""
        p = params
        opt_state = self.opt.init(params)
        losses, counters = [], {}
        for tokens in batches:
            p, opt_state, metrics = self.step(p, opt_state,
                                              jnp.asarray(tokens))
            metrics = jax.device_get(metrics)
            losses.append(float(metrics.pop("loss")))
            for k, v in metrics.items():
                counters[k] = counters.get(k, 0) + int(v)
        self.counters = counters
        if counters:
            trace.note(**counters)
        delta = jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            p, params)
        return delta, (float(np.mean(losses)) if losses else 0.0)
