"""Jit'd dispatch wrappers for the Pallas kernels.

``impl`` (validated by ``kernels.backend.resolve_impl`` — the ONE resolver
shared with backend detection; unknown strings raise eagerly instead of
falling through to the Pallas branch):
  "xla"      pure-jnp implementation (what ``auto`` resolves to on the
             CPU; the twin every compiled kernel is checked against)
  "pallas"   the TPU kernel (compiled for TPU targets)
  "interpret" the TPU kernel executed by the Pallas interpreter on CPU —
             used by the correctness tests to validate the kernel body.

Engine callers resolve ``FLConfig.kernel_backend`` to an impl string once
at construction via ``kernels.backend.resolve_backend``.
"""
from __future__ import annotations


import jax.numpy as jnp

from repro.kernels import fedagg as _fedagg
from repro.kernels import pairscore as _pairscore
from repro.kernels import planner as _planner
from repro.kernels import ref as _ref
from repro.kernels import swa as _swa
from repro.kernels import wkv6 as _wkv6
from repro.kernels.backend import resolve_impl


def weighted_sum(stacked, weights, *, impl: str = "xla",
                 block_n: int = _fedagg.BLOCK_N):
    """stacked (C, *shape); weights (C,) -> (*shape,) fp32 weighted sum.
    ``block_n`` caps the kernel's N tile (``fedagg.block_shape``)."""
    c = stacked.shape[0]
    flat = stacked.reshape(c, -1)
    if resolve_impl(impl) == "xla":
        out = _ref.weighted_sum_ref(flat, weights)
    else:
        out = _fedagg.fedagg_pallas(flat, weights, block_n=block_n,
                                    interpret=(impl == "interpret"))
    return out.reshape(stacked.shape[1:])


def pair_alloc_rates(g_i, g_j, *, n0b: float, pmax: float, bw: float,
                     oma: bool = False, impl: str = "xla"):
    """Fused NOMA pair power allocation + SIC rates (p_i, p_j, r_i, r_j).
    The batched wireless engine's candidate-rate scoring hot path."""
    return _pairscore.pair_alloc_rates(g_i, g_j, n0b=n0b, pmax=pmax, bw=bw,
                                       oma=oma, impl=impl)


def pair_score_matrix(g_strong, g_weak, *, n0b: float, pmax: float,
                      bw: float, impl: str = "xla"):
    """(..., K, N) min-rate candidate scoring table (see kernels.pairscore);
    batches over shared leading dims — the pairing-policy score surface."""
    return _pairscore.pair_score_matrix(g_strong, g_weak, n0b=n0b,
                                        pmax=pmax, bw=bw, impl=impl)


def pair_rate_tables(g_strong, g_weak, *, n0b: float, pmax: float,
                     bw: float, oma: bool = False, impl: str = "xla"):
    """(..., K, N) per-user SIC (or OMA-ablation) rate tables (r_i, r_j)
    for the matching policies' completion-time costs (see
    kernels.pairscore)."""
    return _pairscore.pair_rate_tables(g_strong, g_weak, n0b=n0b,
                                       pmax=pmax, bw=bw, oma=oma,
                                       impl=impl)


def completion_table(g_sorted, t_cmp_sorted, model_bits, *, n0b: float,
                     pmax: float, bw: float, oma: bool = False,
                     impl: str = "xla"):
    """(..., c, c) pair completion-time table over gain-sorted candidates —
    the round planner's shared matching/search surface, one
    ``pair_rate_tables`` call (see kernels.pairscore; DESIGN.md 8.3)."""
    return _pairscore.completion_table(g_sorted, t_cmp_sorted, model_bits,
                                       n0b=n0b, pmax=pmax, bw=bw, oma=oma,
                                       impl=impl)


def planner_tables(g_sorted, t_cmp_sorted, model_bits, *, n0b: float,
                   pmax: float, bw: float, oma: bool = False,
                   impl: str = "xla"):
    """Fused round-planner tables (kernels/planner.py): one pass from
    gain-pairs -> ``_pair_math`` scores -> per-row admission contribution
    -> completion-table tiles. Returns ``(table, row_min, t_sw)``; the
    non-xla table is bf16 (DESIGN.md section 13), reductions fp32."""
    return _planner.planner_tables(g_sorted, t_cmp_sorted, model_bits,
                                   n0b=n0b, pmax=pmax, bw=bw, oma=oma,
                                   impl=impl)


def wkv6(r, k, v, w_log, u, s0=None, *, impl: str = "xla", chunk: int = 64):
    """Chunked RWKV6. Returns (out (B,H,T,C) fp32, s_T). The Pallas path
    currently supports zero initial state (training segments)."""
    b, h, t, c = r.shape
    if s0 is None:
        s0 = jnp.zeros((b, h, c, c), jnp.float32)
    if resolve_impl(impl) == "xla":
        return _ref.wkv6_ref(r, k, v, w_log, u, s0)
    out = _wkv6.wkv6_pallas(r, k, v, w_log, u, chunk=chunk,
                            interpret=(impl == "interpret"))
    # the Pallas kernel carries state internally; recompute s_T cheaply from
    # the ref recurrence only when the caller needs it is wasteful — instead
    # derive s_T from the last chunk analytically is equivalent; for the
    # framework integration (training, fresh segments) s_T is unused.
    return out, None


def swa(q, k, v, *, window: int, impl: str = "xla", softcap: float = 0.0,
        bq: int = 256, bk: int = 256):
    """Sliding-window attention."""
    if resolve_impl(impl) == "xla":
        return _ref.swa_ref(q, k, v, window)
    return _swa.swa_pallas(q, k, v, window=window, bq=bq, bk=bk,
                           softcap=softcap,
                           interpret=(impl == "interpret"))
