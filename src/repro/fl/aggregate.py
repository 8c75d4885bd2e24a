"""Server-side aggregation of client deltas (DESIGN.md section 3).

The FedAvg weighted sum runs, by default, as ONE jitted elementwise fusion
over the list of C delta pytrees: per leaf ``w[0]*d_0 + ... +
w[C-1]*d_{C-1}`` in fp32, in the layout each delta already has. Nothing is
stacked, flattened or padded, so the only HBM traffic is the C deltas read
and the sum written. A stacked (C, N) operand is what the ``fedagg``
Pallas kernel needs, and on a TPU it costs a full copy of the cohort plus
a relayout of every leaf whose minor dimension is not the tiled one (a
576-minor fp32 array is column-tiled on a v5e, so ``reshape(C, -1)``
moves every element); the kernel path (``impl="pallas"`` or
``"interpret"``) stays only behind an explicit request, as the comparison.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.kernels.backend import resolve_impl
from repro.obs import trace


@jax.jit
def _fused_sum(deltas, weights):
    """One fusion per leaf of ``sum_c w[c] * deltas[c]`` in fp32, with the
    weights normalised on device. Jit keys on the list's length, so each
    distinct cohort size C compiles once."""
    w = weights / jnp.maximum(jnp.sum(weights), 1e-9)

    def leaf(*xs):
        acc = w[0] * xs[0].astype(jnp.float32)
        for c in range(1, len(xs)):
            acc = acc + w[c] * xs[c].astype(jnp.float32)
        return acc

    return jax.tree.map(leaf, *deltas)


def aggregate_deltas(deltas: Sequence, weights: np.ndarray, *,
                     impl: str = "xla"):
    """deltas: list of C client update pytrees; weights: (C,), normalised
    here. Returns the aggregated pytree (fp32 leaves of the deltas' own
    shapes). ``impl="xla"`` is the fused sum; ``"pallas"``/``"interpret"``
    stack the deltas for the ``fedagg`` kernel and note the stack's bytes
    as ``stacked_bytes`` on the open trace span."""
    w = jnp.asarray(np.asarray(weights, dtype=np.float32))
    if resolve_impl(impl) == "xla":
        return _fused_sum(list(deltas), w)
    w = w / jnp.maximum(jnp.sum(w), 1e-9)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *deltas)
    trace.note(stacked_bytes=sum(x.nbytes for x in jax.tree.leaves(stacked)))
    return jax.tree.map(lambda s: kops.weighted_sum(s, w, impl=impl), stacked)


def blend_deltas(real_deltas: Sequence, real_weights: np.ndarray,
                 pred_deltas: Sequence, pred_weights: np.ndarray, *,
                 impl: str = "xla"):
    """Aggregate received and server-predicted deltas in one weighted sum.

    ``real_weights`` are the FedAvg data weights of the arrivals;
    ``pred_weights`` must already carry the age-discounted trust
    ``n_c * beta * rho^(A_c - 1)`` (see repro.fl.predictor). Normalization
    happens jointly, so predictions dilute — never displace — real updates.
    With no predictions this reduces exactly to ``aggregate_deltas``.
    """
    deltas = list(real_deltas) + list(pred_deltas)
    weights = np.concatenate([np.asarray(real_weights, np.float64),
                              np.asarray(pred_weights, np.float64)])
    return aggregate_deltas(deltas, weights, impl=impl)


def apply_aggregate(params, agg_delta, server_lr: float = 1.0):
    return jax.tree.map(
        lambda p, d: (p.astype(jnp.float32)
                      + server_lr * d.astype(jnp.float32)).astype(p.dtype),
        params, agg_delta)
