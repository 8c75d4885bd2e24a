"""Server-side update predictor for unselected clients (the paper's third
contribution, Sec. "ANN based FL model prediction").

Every round only J*K clients transmit over the NOMA uplink; the rest keep
training signal the server never sees. The paper trains a server-side ANN
to predict the local model update of each *unselected* client so the
aggregation step sees a full-population view. This module reconstructs that
mechanism in a parameter-efficient form:

  * each arriving flattened delta is embedded by a fixed count-sketch
    (random buckets + signs, norm-preserving in expectation), so the ANN
    input stays ``O(pred_embed_dim)`` regardless of model size;
  * a small MLP (built from ``repro.models.layers`` primitives) maps
    per-client features — sketch of the client's last received delta,
    sketch of this round's aggregate delta, log-staleness, data weight
    (``repro.core.aoi.staleness_features``), norm ratio and cosine
    similarity — to two mixing coefficients ``(a, b)``;
  * the predicted update is the linear reconstruction

        delta_hat_c = a(x_c) * delta_last_c + b(x_c) * delta_agg

    i.e. the ANN learns, per client and per staleness level, how much of
    the client's stale personal direction survives and how much the
    consensus direction has drifted. Because the sketch is linear, the ANN
    trains entirely in sketch space (cheap) while the reconstruction is
    exact in parameter space.
  * training is ONLINE on the server: every client that does arrive is a
    labelled example (features computed from its stored state, target = the
    delta it actually sent), with the LEAVE-ONE-OUT round aggregate in the
    feature row so the label never leaks into its own input. The held-out
    prediction error is measured on those arrivals BEFORE the gradient
    step, so ``History.pred_error`` is honest.

Aggregation blend (see ``repro.fl.aggregate.blend_deltas``): received
deltas keep their FedAvg weight ``n_c``; predicted deltas enter with the
age-discounted weight

    w_c = n_c * beta * rho^(A_c - 1)        (beta = FLConfig.pred_blend,
                                             rho  = FLConfig.pred_discount)

so stale predictions fade geometrically and a prediction can never
outweigh a real update. ``predictor="stale"`` is the ablation baseline
that reuses the last received delta verbatim (a=1, b=0) under the same
blend — isolating what the ANN adds beyond plain staleness reuse.

Config knobs live on ``FLConfig`` (``predictor``, ``pred_embed_dim``,
``pred_hidden_dim``, ``pred_lr``, ``pred_steps``, ``pred_discount``,
``pred_blend``, ``pred_max_age``).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from repro.configs.base import FLConfig, check_choice
from repro.core import aoi
from repro.models.layers import dense_init, zeros_init
from repro.optim import AdamW, apply_updates

_EPS = 1e-12
_N_SCALARS = 4  # log-staleness, data weight, log norm ratio, cosine


# ---------------------------------------------------------------------------
# sketch + MLP
# ---------------------------------------------------------------------------


def make_sketch(n_params: int, dim: int, seed: int):
    """Count-sketch projection R^P -> R^dim: random bucket + random sign per
    coordinate. Linear, O(P) memory, and E||Sx||^2 = ||x||^2."""
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(rng.integers(0, dim, n_params), dtype=jnp.int32)
    sign = jnp.asarray(rng.choice(np.float32([-1.0, 1.0]), n_params))

    @jax.jit
    def sk(vec):
        return jax.ops.segment_sum(vec * sign, idx, num_segments=dim)

    return sk


def init_mlp(key, d_in: int, d_hidden: int):
    """Two-hidden-layer MLP; the head is zero-initialized with bias
    (0.5, 0.5) so the untrained predictor already outputs the sane prior
    0.5*last + 0.5*aggregate."""
    ks = jax.random.split(key, 3)
    return {
        "w1": dense_init(ks[0], (d_in, d_hidden), jnp.float32),
        "b1": zeros_init((d_hidden,), jnp.float32),
        "w2": dense_init(ks[1], (d_hidden, d_hidden), jnp.float32),
        "b2": zeros_init((d_hidden,), jnp.float32),
        "w3": zeros_init((d_hidden, 2), jnp.float32),
        "b3": jnp.array([0.5, 0.5], jnp.float32),
    }


def mlp_coeffs(params, x):
    """x (M, d_in) -> (a, b) each (M,), clipped for aggregation safety."""
    h = jax.nn.silu(x @ params["w1"] + params["b1"])
    h = jax.nn.silu(h @ params["w2"] + params["b2"])
    out = h @ params["w3"] + params["b3"]
    return jnp.clip(out[:, 0], -2.0, 2.0), jnp.clip(out[:, 1], -2.0, 2.0)


# ---------------------------------------------------------------------------
# predictor
# ---------------------------------------------------------------------------


class UpdatePredictor:
    """Per-client last-delta store + online-trained coefficient ANN.

    The store keeps one flattened fp32 delta per known client (simulation
    scale; the real system would keep the same buffer it already holds for
    secure aggregation). All learning state is fp32 and host-driven.
    """

    def __init__(self, params_template, fl: FLConfig, n_clients: int, *,
                 mode: Optional[str] = None, seed: int = 0):
        self.mode = check_choice("predictor",
                                 fl.predictor if mode is None else mode)
        self.fl = fl
        self.n_clients = n_clients

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params_template)
        flat0, self._unravel = ravel_pytree(zeros)
        self.n_params = int(flat0.size)
        self.embed_dim = min(fl.pred_embed_dim, self.n_params)
        self.sketch = make_sketch(self.n_params, self.embed_dim,
                                  seed + 20_000)

        # per-client state (None until the first real delta arrives)
        self._last_flat: list = [None] * n_clients
        self._last_sk: list = [None] * n_clients

        self.d_in = 2 * self.embed_dim + _N_SCALARS
        self.net = init_mlp(jax.random.PRNGKey(seed + 20_001),
                            self.d_in, fl.pred_hidden_dim)
        self.opt = AdamW(lr=fl.pred_lr, weight_decay=0.0)
        self.opt_state = self.opt.init(self.net)
        self._train_step = self._make_train_step()

    # -- state -------------------------------------------------------------
    def has(self, client: int) -> bool:
        return self._last_flat[client] is not None

    def known(self) -> np.ndarray:
        return np.array([d is not None for d in self._last_flat])

    def flatten(self, delta_tree):
        flat, _ = ravel_pytree(delta_tree)
        return flat.astype(jnp.float32)

    def unflatten(self, flat):
        return self._unravel(flat)

    # -- features ----------------------------------------------------------
    def _features(self, clients: Sequence[int], ages: np.ndarray,
                  data_weights: np.ndarray, sk_mean):
        """Rows of ANN input for ``clients`` (all must have history).

        ``sk_mean`` is either one shared aggregate sketch (E,) or one row
        per client (M, E) — the latter is the leave-one-out means used in
        training so the target never leaks into its own features."""
        stale = aoi.staleness_features(ages, data_weights)  # (N, 2)
        sl = jnp.stack([self._last_sk[c] for c in clients])  # (M, E)
        sm = jnp.broadcast_to(jnp.atleast_2d(sk_mean), sl.shape)
        nl = jnp.linalg.norm(sl, axis=1, keepdims=True) + _EPS
        nm = jnp.linalg.norm(sm, axis=1, keepdims=True) + _EPS
        cos = jnp.sum((sl / nl) * (sm / nm), axis=1)
        scalars = jnp.stack(
            [jnp.asarray(stale[list(clients), 0], jnp.float32),
             jnp.asarray(stale[list(clients), 1], jnp.float32),
             jnp.log(nl[:, 0] / nm[:, 0]),
             cos], axis=1)
        return jnp.concatenate([sl / nl, sm / nm, scalars], axis=1), sl

    # -- online training ---------------------------------------------------
    def _make_train_step(self):
        opt = self.opt

        @jax.jit
        def step(net, opt_state, x, sk_last, sk_mean, sk_true):
            def loss_fn(p):
                a, b = mlp_coeffs(p, x)
                pred = a[:, None] * sk_last + b[:, None] * sk_mean
                num = jnp.sum((pred - sk_true) ** 2, axis=1)
                den = jnp.sum(sk_true ** 2, axis=1) + _EPS
                return jnp.mean(num / den)

            loss, grads = jax.value_and_grad(loss_fn)(net)
            upd, opt_state = opt.update(grads, opt_state, net)
            return apply_updates(net, upd), opt_state, loss

        return step

    def train_on(self, x, sk_last, sk_mean, sk_true, steps: int = 1):
        """Run ``steps`` optimizer steps on one labelled batch; returns the
        loss of the FIRST step (pre-update loss of this batch)."""
        first = None
        for _ in range(max(1, steps)):
            self.net, self.opt_state, loss = self._train_step(
                self.net, self.opt_state, x, sk_last, sk_mean, sk_true)
            first = float(loss) if first is None else first
        return first

    # -- round interface ---------------------------------------------------
    def observe(self, clients: Sequence[int], flat_deltas: Sequence,
                ages: np.ndarray, data_weights: np.ndarray) -> dict:
        """Ingest the deltas that actually arrived this round.

        Returns ``{"pred_loss", "pred_error"}`` where ``pred_error`` is the
        mean relative sketch-space error of predicting the arrivals from
        their PRE-round state (held-out: measured before the store update
        and before the gradient step). Both the error and the training
        examples use the LEAVE-ONE-OUT aggregate — the client's own delta
        is removed from its sk_mean row, matching serving time where the
        predicted client contributed nothing to the round aggregate.
        """
        clients = [int(c) for c in clients]
        sk_new = [self.sketch(f) for f in flat_deltas]
        w = np.asarray([data_weights[c] for c in clients], np.float64)
        w = w / max(w.sum(), _EPS)
        sk_mean = sum(wi * s for wi, s in zip(w, sk_new))

        stats = {"pred_loss": float("nan"), "pred_error": float("nan")}
        # LOO is undefined for a lone arrival (w ~ 1): no other update to
        # form an aggregate from, so such rows are dropped rather than fed
        # to the MLP as degenerate zero-aggregate examples
        hist = [i for i, c in enumerate(clients)
                if self.has(c) and w[i] < 1.0 - 1e-6]
        if hist and self.mode in ("stale", "ann"):
            loo = jnp.stack([
                (sk_mean - w[i] * sk_new[i]) / (1.0 - w[i])
                for i in hist])
            x, sl = self._features([clients[i] for i in hist], ages,
                                   data_weights, loo)
            st = jnp.stack([sk_new[i] for i in hist])
            if self.mode == "ann":
                a, b = mlp_coeffs(self.net, x)
            else:
                a = jnp.ones(len(hist))
                b = jnp.zeros(len(hist))
            pred = a[:, None] * sl + b[:, None] * loo
            err = jnp.linalg.norm(pred - st, axis=1) \
                / (jnp.linalg.norm(st, axis=1) + _EPS)
            stats["pred_error"] = float(jnp.mean(err))
            if self.mode == "ann":
                stats["pred_loss"] = self.train_on(
                    x, sl, loo, st, steps=self.fl.pred_steps)
        for c, f, s in zip(clients, flat_deltas, sk_new):
            self._last_flat[c] = f
            self._last_sk[c] = s
        return stats

    def predictable(self, selected: np.ndarray, ages: np.ndarray
                    ) -> np.ndarray:
        """Client ids eligible for prediction this round: unselected, with
        a stored delta, and (if ``pred_max_age`` > 0) not too stale."""
        mask = self.known() & ~np.asarray(selected, bool)
        if self.fl.pred_max_age > 0:
            mask &= np.asarray(ages) <= self.fl.pred_max_age
        return np.flatnonzero(mask)

    def predict(self, clients: Sequence[int], ages: np.ndarray,
                data_weights: np.ndarray, mean_flat) -> list:
        """Predicted flattened deltas for ``clients`` (each must have
        history). ``mean_flat`` is this round's aggregated received delta."""
        clients = [int(c) for c in clients]
        if not clients:
            return []
        sk_mean = self.sketch(mean_flat)
        if self.mode == "stale":
            return [self._last_flat[c] for c in clients]
        x, _ = self._features(clients, ages, data_weights, sk_mean)
        a, b = mlp_coeffs(self.net, x)
        a = np.asarray(a)
        b = np.asarray(b)
        return [a[i] * self._last_flat[c] + b[i] * mean_flat
                for i, c in enumerate(clients)]
