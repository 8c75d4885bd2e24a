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


# The host dispatches a client's SGD steps ahead of the device, so that
# no step waits for the host to dispatch it. Nothing is donated, so each
# step queued behind the running one holds one more copy of the weights:
# two steps are in flight (before step i the host waits for step i - 2)
# while that copy is at most RUN_AHEAD_BYTES, else one (the host waits for
# step i - 1, with step i's batch already sent). On a v5e the second step
# in flight raised peak HBM 2.78 -> 3.88 GB for smollm-135M's 0.54 GB of
# weights and 12.40 -> 14.37 GB for Moonlight's 2.27 GB share.
RUN_AHEAD_BYTES = 1 << 30


class LocalTrainer:
    """Runs E local epochs of SGD for one client, returns the model DELTA
    (the uplink payload in the real system)."""

    def __init__(self, cfg: ModelConfig, lr: float, momentum: float = 0.0):
        self.cfg = cfg
        self.opt, self.step = make_sgd_batch_step(cfg, lr, momentum)
        self.counters = {}

    def local_update(self, params, batches: Iterable[np.ndarray]):
        """-> (fp32 delta, mean loss). The steps are dispatched with one
        or two on the device (``RUN_AHEAD_BYTES``; the host waits for a
        step to finish, reading nothing), their metrics left there; the
        delta is dispatched next, and only then do all the steps' metrics
        come to the host, in one transfer. The loss's mean and the other
        counters' sums (``self.counters``, Python ints) are reduced there,
        and noted on the open span with ``pulls``, the transfers made (1,
        or 0 for no step)."""
        p = params
        opt_state = self.opt.init(params)
        in_flight = 2 if sum(x.nbytes for x in jax.tree.leaves(params)) \
            <= RUN_AHEAD_BYTES else 1
        metrics = []
        for i, tokens in enumerate(batches):
            tokens = jnp.asarray(tokens)
            if i >= in_flight:
                metrics[i - in_flight]["loss"].block_until_ready()
            p, opt_state, m = self.step(p, opt_state, tokens)
            metrics.append(m)
        delta = jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            p, params)
        metrics = jax.device_get(metrics)
        losses = [float(m.pop("loss")) for m in metrics]
        counters = {}
        for m in metrics:
            for k, v in m.items():
                counters[k] = counters.get(k, 0) + int(v)
        self.counters = counters
        trace.note(pulls=int(bool(metrics)), **counters)
        return delta, (float(np.mean(losses)) if losses else 0.0)
