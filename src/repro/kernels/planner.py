"""Pallas TPU kernel: fused round-planner tables.

One pass over gain-sorted candidates produces everything the engine's
matching/search stages consume, replacing the three separate XLA passes the
fast path did before (broadcasted ``_pair_math`` rate tables -> completion
assembly -> strong_weak bottleneck reduction):

    table[p, q]  = max(t_p + S/R_i(p,q), t_q + S/R_j(p,q))   (p strong,
                   q weak, closed-form max-min NOMA power)    bf16 tiles
    row_min[p]   = min_q!=p table[p, q]                       fp32
    t_sw         = max_{p<m} table[p, c_pair-1-p]             fp32

``row_min`` is the per-row admission contribution — each candidate's
best-case pair completion, the score a completion-aware admission stage
ranks by. ``t_sw`` is the strong_weak anti-diagonal bottleneck, the
never-slower guard the hungarian pairing compares candidate matchings
against (``core/engine.py _fast_finish``).

Mixed-precision contract (DESIGN.md section 13): pair math, reductions and
threshold comparisons run in fp32 inside the kernel; only the O(c^2) table
tiles are stored bf16. ``row_min``/``t_sw`` are reduced from the fp32
values BEFORE the bf16 round-trip, so the scalar decisions the planner
makes are full fp32; the table itself carries bf16's ~3 decimal digits,
validated against the fp64 numpy reference in the parity tier.

Tiling: grid (B, c/128); each step holds the full strong-side gain column
(cp, 1) plus a 128-lane weak-side slab and emits one (cp, 128) table tile.
The strong side is handed in already as a column, so the kernel needs no
lane-to-sublane relayout. Row/column reductions accumulate across column
steps into revisited output blocks (sequential grid order, ``@pl.when``
first-step init — the fedagg/pairscore idiom); ``t_sw`` is kept as a
lane-dense (1, 128) row because Mosaic cannot store a scalar to VMEM.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.pairscore import _pair_math

LANES = 128
_EPS = 1e-9          # rate floor shared with pairscore.completion_table


def _planner_kernel(gr_ref, tr_ref, gc_ref, tc_ref, mb_ref,
                    tab_ref, rmin_ref, tsw_ref, *,
                    n0b, pmax, bw, oma, c, m, c_pair):
    j = pl.program_id(1)
    cp = gr_ref.shape[1]
    g_rows = gr_ref[0]                       # (cp, 1) strong-side gains
    t_rows = tr_ref[0]
    g_cols = gc_ref[0]                       # (1, LANES) weak-side gains
    t_cols = tc_ref[0]
    mb = mb_ref[0]                           # (1, LANES), one value per row

    gi = jnp.broadcast_to(g_rows, (cp, LANES))
    gj = jnp.broadcast_to(g_cols, (cp, LANES))
    _, _, r_i, r_j = _pair_math(gi, gj, n0b=n0b, pmax=pmax, bw=bw, oma=oma)
    comp = jnp.maximum(t_rows + mb / jnp.maximum(r_i, _EPS),
                       t_cols + mb / jnp.maximum(r_j, _EPS))
    tab_ref[0] = comp.astype(tab_ref.dtype)

    rowid = jax.lax.broadcasted_iota(jnp.int32, (cp, LANES), 0)
    colid = jax.lax.broadcasted_iota(jnp.int32, (cp, LANES), 1) + j * LANES
    valid = (rowid < c) & (colid < c) & (rowid != colid)
    rm = jnp.min(jnp.where(valid, comp, jnp.inf), axis=1,
                 keepdims=True)                                  # (cp, 1)
    # strong_weak anti-diagonal: rank p pairs with rank c_pair-1-p; the
    # strong half (p < m) hits each pair's table entry exactly once.
    pair_m = (colid == c_pair - 1 - rowid) & (rowid < m)
    tmax = jnp.max(jnp.where(pair_m, comp, -jnp.inf), axis=0, keepdims=True)
    tmax = jnp.broadcast_to(jnp.max(tmax, axis=1, keepdims=True),
                            (1, LANES))                          # lane-dense

    @pl.when(j == 0)
    def _init():
        rmin_ref[0] = rm
        tsw_ref[0] = tmax

    @pl.when(j > 0)
    def _acc():
        rmin_ref[0] = jnp.minimum(rmin_ref[0], rm)
        tsw_ref[0] = jnp.maximum(tsw_ref[0], tmax)


@functools.partial(
    jax.jit, static_argnames=("n0b", "pmax", "bw", "oma", "interpret",
                              "table_dtype"))
def planner_tables_pallas(g_sorted, t_cmp_sorted, model_bits, *,
                          n0b: float, pmax: float, bw: float,
                          oma: bool = False, interpret: bool = False,
                          table_dtype=jnp.bfloat16
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused (table, row_min, t_sw) over (..., c) gain-sorted candidates.

    ``table`` (..., c, c) ``table_dtype``; ``row_min`` (..., c) fp32;
    ``t_sw`` (...,) fp32. ``model_bits`` broadcasts over the leading dims.
    Pads c to 128-lane tiles; padding rows/columns carry finite garbage
    (zero gain -> huge-but-finite completion) and are sliced off here and
    masked out of every reduction in-kernel.
    """
    g = jnp.asarray(g_sorted, jnp.float32)
    t = jnp.asarray(t_cmp_sorted, jnp.float32)
    assert g.shape == t.shape, (g.shape, t.shape)
    lead, c = g.shape[:-1], g.shape[-1]
    b = 1
    for d in lead:
        b *= d
    mb = jnp.broadcast_to(jnp.asarray(model_bits, jnp.float32), lead)
    g2 = g.reshape(b, c)
    t2 = t.reshape(b, c)
    cp = c + (-c) % LANES
    if cp != c:
        g2 = jnp.pad(g2, ((0, 0), (0, cp - c)))
        t2 = jnp.pad(t2, ((0, 0), (0, cp - c)))
    c_pair = c - (c % 2)
    m = c_pair // 2
    # Every block's last two dims are (8k, 128k) or the array's own: the
    # strong side enters as (b, cp, 1) columns, the weak side as (b, 1, cp)
    # rows, model_bits and t_sw as lane-dense (b, 1, LANES) rows.
    def per_row(i, j):
        return i, 0, 0

    full_col = pl.BlockSpec((1, cp, 1), per_row)
    slab = pl.BlockSpec((1, 1, LANES), lambda i, j: (i, 0, j))
    lane_row = pl.BlockSpec((1, 1, LANES), per_row)
    kernel = functools.partial(_planner_kernel, n0b=n0b, pmax=pmax, bw=bw,
                               oma=oma, c=c, m=m, c_pair=c_pair)
    tab, rmin, tsw = pl.pallas_call(
        kernel,
        grid=(b, cp // LANES),
        in_specs=[full_col, full_col, slab, slab, lane_row],
        out_specs=(pl.BlockSpec((1, cp, LANES), lambda i, j: (i, 0, j)),
                   full_col, lane_row),
        out_shape=(jax.ShapeDtypeStruct((b, cp, cp), table_dtype),
                   jax.ShapeDtypeStruct((b, cp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, LANES), jnp.float32)),
        interpret=interpret,
    )(g2[:, :, None], t2[:, :, None], g2[:, None, :], t2[:, None, :],
      jnp.broadcast_to(mb.reshape(b, 1, 1), (b, 1, LANES)))
    table = tab[:, :c, :c].reshape(lead + (c, c))
    row_min = rmin[:, :c, 0].reshape(lead + (c,))
    t_sw = tsw[:, 0, 0].reshape(lead)
    if m == 0:          # no pairs (c <= 1): the -inf identity never updates
        t_sw = jnp.zeros_like(t_sw)
    return table, row_min, t_sw


def planner_tables_ref(g_sorted, t_cmp_sorted, model_bits, *,
                       n0b: float, pmax: float, bw: float,
                       oma: bool = False
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """XLA twin of ``planner_tables_pallas`` — same outputs, full fp32 (no
    bf16 table round-trip), built from the unfused passes. The parity tier
    pins kernel == twin; the twin is what ``impl="xla"`` dispatches to."""
    from repro.kernels import pairscore
    g = jnp.asarray(g_sorted, jnp.float32)
    t = jnp.asarray(t_cmp_sorted, jnp.float32)
    c = g.shape[-1]
    mb = jnp.broadcast_to(jnp.asarray(model_bits, jnp.float32), g.shape[:-1])
    table = pairscore.completion_table(g, t, mb, n0b=n0b, pmax=pmax, bw=bw,
                                       oma=oma, impl="xla")
    eye = jnp.eye(c, dtype=bool)
    row_min = jnp.min(jnp.where(eye, jnp.inf, table), axis=-1)
    c_pair = c - (c % 2)
    m = c_pair // 2
    if m == 0:
        t_sw = jnp.zeros(g.shape[:-1], jnp.float32)
    else:
        ranks = jnp.arange(m)
        anti = table[..., ranks, c_pair - 1 - ranks]
        t_sw = jnp.max(anti, axis=-1)
    return table, row_min, t_sw


def planner_tables(g_sorted, t_cmp_sorted, model_bits, *, n0b: float,
                   pmax: float, bw: float, oma: bool = False,
                   impl: str = "xla", table_dtype=jnp.bfloat16):
    """Dispatch: ``impl`` in {"xla", "pallas", "interpret"} (ops.py idiom);
    eager ValueError on anything else via the shared resolver."""
    from repro.kernels.backend import resolve_impl
    if resolve_impl(impl) == "xla":
        return planner_tables_ref(g_sorted, t_cmp_sorted, model_bits,
                                  n0b=n0b, pmax=pmax, bw=bw, oma=oma)
    return planner_tables_pallas(g_sorted, t_cmp_sorted, model_bits,
                                 n0b=n0b, pmax=pmax, bw=bw, oma=oma,
                                 interpret=(impl == "interpret"),
                                 table_dtype=table_dtype)
