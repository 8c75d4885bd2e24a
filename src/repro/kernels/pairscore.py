"""Pallas TPU kernel: closed-form NOMA pair power allocation + SIC rate
scoring — the O(N·K) candidate-rate hot path of the batched wireless engine.

For every (strong, weak) gain pair the kernel fuses the max-min power
allocation (stable conjugate form of the quadratic root, DESIGN.md
section 4.3) with the SIC rate formulas into one VPU pass:

    y*  = 2 P g_i N0B / (N0B + sqrt(N0B^2 + 4 P g_i N0B))
    p_j = min(y* / g_j, P)                    p_i = P
    R_i = B log2(1 + p_i g_i / (p_j g_j + N0B))
    R_j = B log2(1 + p_j g_j / N0B)

Arithmetic intensity is ~10 flop/byte of transcendental-light work, so the
design follows the ``kernels/fedagg.py`` bandwidth-oriented tiling idiom
(DESIGN.md section 3): the flattened pair axis is padded to (8, 128)
fp32 tiles and the grid walks row-blocks, double-buffered by the pipeline.

``_pair_math`` is the single source of truth: the kernel body and the XLA
twin (used by the engine's pure-jnp path and the parity tests) call the
same function, so "jax" and "jax+pallas" engine modes agree bitwise up to
scheduling.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LN2 = 0.6931471805599453
BLOCK_R = 8      # sublanes per tile (fp32 min tile is (8, 128))
LANES = 128


# ---------------------------------------------------------------------------
# shared math (kernel body == XLA twin)
# ---------------------------------------------------------------------------


def _pair_math(g_i, g_j, *, n0b: float, pmax: float, bw: float,
               oma: bool = False):
    """(p_i, p_j, r_i, r_j) for strong/weak gain arrays, elementwise.

    Matches ``core.noma.pair_power_allocation`` + ``pair_rates`` (or
    ``oma_pair_rates``) but uses the cancellation-free conjugate root and
    log1p so the fp32 device path tracks the fp64 numpy reference.
    """
    if oma:
        p_i = jnp.full_like(g_i, pmax)
        p_j = jnp.full_like(g_j, pmax)
        r_i = 0.5 * bw * jnp.log1p(pmax * g_i / n0b) / LN2
        r_j = 0.5 * bw * jnp.log1p(pmax * g_j / n0b) / LN2
        return p_i, p_j, r_i, r_j
    y = 2.0 * pmax * g_i * n0b / (
        n0b + jnp.sqrt(n0b * n0b + 4.0 * pmax * g_i * n0b))
    p_j = jnp.minimum(y / jnp.maximum(g_j, 1e-30), pmax)
    p_i = jnp.full_like(g_i, pmax)
    r_i = bw * jnp.log1p(p_i * g_i / (p_j * g_j + n0b)) / LN2
    r_j = bw * jnp.log1p(p_j * g_j / n0b) / LN2
    return p_i, p_j, r_i, r_j


def solo_rate_math(g, *, n0b: float, pmax: float, bw: float):
    """Full-subchannel single-user rate (matches ``core.noma.solo_rate``)."""
    return bw * jnp.log1p(pmax * g / n0b) / LN2


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------


def _pairscore_kernel(gi_ref, gj_ref, pi_ref, pj_ref, ri_ref, rj_ref, *,
                      n0b, pmax, bw, oma):
    g_i = gi_ref[...].astype(jnp.float32)
    g_j = gj_ref[...].astype(jnp.float32)
    p_i, p_j, r_i, r_j = _pair_math(g_i, g_j, n0b=n0b, pmax=pmax, bw=bw,
                                    oma=oma)
    pi_ref[...] = p_i
    pj_ref[...] = p_j
    ri_ref[...] = r_i
    rj_ref[...] = r_j


@functools.partial(jax.jit, static_argnames=("n0b", "pmax", "bw", "oma",
                                             "interpret"))
def pairscore_pallas(g_i, g_j, *, n0b: float, pmax: float, bw: float,
                     oma: bool = False, interpret: bool = False
                     ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused (p_i, p_j, r_i, r_j) over arbitrary-shape gain arrays.

    Flattens, zero-pads to (8, 128) fp32 tiles, walks row-blocks
    (fedagg idiom), then restores the caller's shape.
    """
    assert g_i.shape == g_j.shape, (g_i.shape, g_j.shape)
    shape = g_i.shape
    flat_i = g_i.reshape(-1).astype(jnp.float32)
    flat_j = g_j.reshape(-1).astype(jnp.float32)
    size = flat_i.size
    tile = BLOCK_R * LANES
    pad = (-size) % tile
    if pad:
        flat_i = jnp.pad(flat_i, (0, pad))
        flat_j = jnp.pad(flat_j, (0, pad))
    rows = (size + pad) // LANES
    gi2 = flat_i.reshape(rows, LANES)
    gj2 = flat_j.reshape(rows, LANES)
    grid = (rows // BLOCK_R,)
    spec = pl.BlockSpec((BLOCK_R, LANES), lambda i: (i, 0))
    out_sds = jax.ShapeDtypeStruct((rows, LANES), jnp.float32)
    kernel = functools.partial(_pairscore_kernel, n0b=n0b, pmax=pmax, bw=bw,
                               oma=oma)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=(spec, spec, spec, spec),
        out_shape=(out_sds, out_sds, out_sds, out_sds),
        interpret=interpret,
        name="pairscore",
    )(gi2, gj2)
    return tuple(o.reshape(-1)[:size].reshape(shape) for o in outs)


def pair_alloc_rates(g_i, g_j, *, n0b: float, pmax: float, bw: float,
                     oma: bool = False, impl: str = "xla"):
    """Dispatch: ``impl`` in {"xla", "pallas", "interpret"} (ops.py idiom);
    eager ValueError on anything else via the shared resolver."""
    from repro.kernels.backend import resolve_impl
    if resolve_impl(impl) == "xla":
        return _pair_math(jnp.asarray(g_i, jnp.float32),
                          jnp.asarray(g_j, jnp.float32),
                          n0b=n0b, pmax=pmax, bw=bw, oma=oma)
    return pairscore_pallas(jnp.asarray(g_i), jnp.asarray(g_j), n0b=n0b,
                            pmax=pmax, bw=bw, oma=oma,
                            interpret=(impl == "interpret"))


def pair_rate_tables(g_strong, g_weak, *, n0b: float, pmax: float,
                     bw: float, oma: bool = False, impl: str = "xla"
                     ) -> Tuple[jax.Array, jax.Array]:
    """(..., K, N) per-user SIC rate tables (r_i, r_j): entry [k, n] is the
    pair (strong user k, weak user n) under closed-form max-min power.
    ``g_strong`` (..., K) and ``g_weak`` (..., N) batch over any shared
    leading dims. Feeds the matching-based pairing policies' completion
    -time cost tables (core/pairing.py, core/matching.py)."""
    from repro.kernels.backend import resolve_impl
    resolve_impl(impl)
    g_strong = jnp.asarray(g_strong)
    g_weak = jnp.asarray(g_weak)
    k = g_strong.shape[-1]
    n = g_weak.shape[-1]
    shape = g_strong.shape[:-1] + (k, n)
    gi = jnp.broadcast_to(g_strong[..., :, None], shape)
    gj = jnp.broadcast_to(g_weak[..., None, :], shape)
    _, _, r_i, r_j = pair_alloc_rates(gi, gj, n0b=n0b, pmax=pmax, bw=bw,
                                      oma=oma, impl=impl)
    return r_i, r_j


def completion_table(g_sorted, t_cmp_sorted, model_bits, *, n0b: float,
                     pmax: float, bw: float, oma: bool = False,
                     impl: str = "xla") -> jax.Array:
    """(..., c, c) pair completion-time table over gain-sorted candidates:
    entry [p, q] = max over the two users of T_cmp + S/R with rank p
    strong, rank q weak, under closed-form max-min power. Built on ONE
    ``pair_rate_tables`` call — the shared matching/search surface of the
    round planner (numpy twin: ``pairing.completion_table``; DESIGN.md
    8.3). ``model_bits`` broadcasts over the leading batch dims.

    Non-xla impls route to the fused planner kernel (kernels/planner.py)
    and return its bf16 tiles upcast to fp32 — the mixed-precision
    contract of DESIGN.md section 13."""
    from repro.kernels.backend import resolve_impl
    if resolve_impl(impl) != "xla":
        from repro.kernels import planner
        table, _, _ = planner.planner_tables(
            g_sorted, t_cmp_sorted, model_bits, n0b=n0b, pmax=pmax, bw=bw,
            oma=oma, impl=impl)
        return table.astype(jnp.float32)
    r_i, r_j = pair_rate_tables(g_sorted, g_sorted, n0b=n0b, pmax=pmax,
                                bw=bw, oma=oma, impl=impl)
    mb = jnp.asarray(model_bits)[..., None, None]
    t = jnp.asarray(t_cmp_sorted)
    return jnp.maximum(t[..., :, None] + mb / jnp.maximum(r_i, 1e-9),
                       t[..., None, :] + mb / jnp.maximum(r_j, 1e-9))


def effective_power_table(g_strong, g_weak, *, n0b: float,
                          pmax: float) -> jax.Array:
    """(..., K, N) table of min(y*(g_i), P g_j) — the strictly monotone
    min-rate surrogate whose structural ties are precision-exact (the
    greedy pairing policy's score surface; numpy twin in
    ``core.pairing.effective_power_table``)."""
    g_i = jnp.asarray(g_strong)
    y = 2.0 * pmax * g_i * n0b / (
        n0b + jnp.sqrt(n0b * n0b + 4.0 * pmax * g_i * n0b))
    return jnp.minimum(y[..., :, None],
                       pmax * jnp.asarray(g_weak)[..., None, :])


def pair_score_matrix(g_strong, g_weak, *, n0b: float, pmax: float,
                      bw: float, impl: str = "xla") -> jax.Array:
    """(..., K, N) min-rate table: score[k, n] = min SIC rate when candidate
    n is the weak partner of strong user k — the candidate-rate scoring
    surface for matching-based pairing policies and the engine benchmark.
    Batches over any shared leading dims of the gain vectors."""
    r_i, r_j = pair_rate_tables(g_strong, g_weak, n0b=n0b, pmax=pmax,
                                bw=bw, impl=impl)
    return jnp.minimum(r_i, r_j)
