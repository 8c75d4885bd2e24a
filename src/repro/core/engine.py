"""Batched JAX wireless engine: the paper's joint round (AoU selection,
strong/weak SIC pairing, closed-form power allocation, budget eviction) as a
jit/vmap-able function of fixed-shape arrays.

The staged round planner (``core/plan.py``) is the numpy fp64 semantic
reference — score -> admit -> match -> allocate -> time, DESIGN.md
section 8; this module transcribes each stage into fixed-shape twins so
thousands of Monte-Carlo channel drops run in one XLA call instead of a
Python loop (DESIGN.md section 5):

  * Python pair lists        -> fixed (P,) strong/weak index arrays, -1 pad;
  * odd candidate counts     -> weakest candidate on a solo subchannel,
                                encoded as a (solo, -1) row;
  * the eviction/backfill loop -> ``lax.while_loop`` over a boolean
                                candidate mask + a monotone backfill cursor
                                into the priority order (the numpy re-scan
                                of ``order[slots:]`` always takes the next
                                never-admitted client, so a cursor is exact);
  * candidate-rate scoring   -> ``kernels/pairscore.py`` (Pallas path) or
                                its XLA twin — identical math either way;
  * subchannel pairing       -> ``FLConfig.pairing`` policy: strong_weak /
                                adjacent as index math, hungarian /
                                greedy_matching via the batched assignment
                                solvers in ``core/matching.py`` over the
                                pair score tables (DESIGN.md section 7);
  * admitted-set selection   -> ``FLConfig.selection``: ``greedy_set``
                                threshold admission, or ``joint``
                                pairing-aware refinement (exhaustive
                                enumeration / swap search over the shared
                                ``plan.enumerate_subsets`` static tables +
                                the ``_pick_faster`` never-worse guard,
                                DESIGN.md section 8).

Precision: the engine runs fp32 on device while the reference is fp64 numpy.
The power-allocation root uses the cancellation-free conjugate form and
rates use log1p, so parity holds to ~1e-6 relative on generic inputs; exact
ties in priorities/gains (measure-zero under continuous fading) may resolve
differently — see DESIGN.md section 5.4.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ADMISSIONS, FLConfig, NOMAConfig
from repro.core import matching
from repro.core.pairing import ENUM_MAX_PAIRS, PAIRINGS, enumerate_matchings
from repro.core.plan import (
    JOINT_ENUM_MAX_N,
    JOINT_SWAP_ITERS,
    SELECTIONS,
    RoundEnv,
    Schedule,
    cell_capacity,
    enumerate_subsets,
    resolve_admission,
)
from repro.kernels import pairscore, planner
from repro.kernels.backend import resolve_backend
from repro.obs import trace
from repro.obs.metrics import AOU_BUCKET_EDGES


# ---------------------------------------------------------------------------
# static parameters
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EngineParams:
    """Hashable scalars baked into the jitted core (static argnums)."""
    slots: int               # K * J candidate slots
    bandwidth_hz: float
    noise_power_w: float     # N0 * B
    max_power_w: float
    cycles_per_sample: float
    local_epochs: int
    ref_path_loss: float
    path_loss_exp: float
    min_radius_m: float
    cell_radius_m: float

    @classmethod
    def from_configs(cls, ncfg: NOMAConfig, flcfg: FLConfig
                     ) -> "EngineParams":
        return cls(
            slots=ncfg.n_subchannels * ncfg.users_per_subchannel,
            bandwidth_hz=ncfg.bandwidth_hz,
            noise_power_w=ncfg.noise_density * ncfg.bandwidth_hz,
            max_power_w=ncfg.max_power_w,
            cycles_per_sample=flcfg.cpu_cycles_per_sample,
            local_epochs=flcfg.local_epochs,
            ref_path_loss=ncfg.ref_path_loss,
            path_loss_exp=ncfg.path_loss_exp,
            min_radius_m=ncfg.min_radius_m,
            cell_radius_m=ncfg.cell_radius_m,
        )


class EngineSchedule(NamedTuple):
    """Fixed-shape Schedule: arrays carry a leading batch dim B.

    ``pair_strong/pair_weak`` are (B, P) int32; row p is a real SIC pair when
    ``pair_weak[p] >= 0``, a solo subchannel when ``pair_strong[p] >= 0 >
    pair_weak[p]``, padding when ``pair_strong[p] < 0``.
    """
    selected: jax.Array      # (B, N) bool
    pair_strong: jax.Array   # (B, P) int32
    pair_weak: jax.Array     # (B, P) int32
    rates: jax.Array         # (B, N) f32 bits/s (0 unselected)
    powers: jax.Array        # (B, N) f32 W
    t_cmp: jax.Array         # (B, N) f32 s
    t_com: jax.Array         # (B, N) f32 s
    t_round: jax.Array       # (B,)   f32 s
    agg_weights: jax.Array   # (B, N) f32
    evicted: jax.Array       # (B, N) bool (budget-loop evictions)


# ---------------------------------------------------------------------------
# diagnostics (numpy reference: ``plan.schedule_diag``)
# ---------------------------------------------------------------------------


def _aou_histogram(ages):
    """Fixed-shape AoU bucket counts, jax twin of
    ``metrics.aou_histogram``: ages (..., N) -> int32 counts
    (..., len(AOU_BUCKET_EDGES) + 1), identical bucketing (bucket i is
    ages in (edge[i-1], edge[i]], last bucket > edge[-1])."""
    edges = jnp.asarray(AOU_BUCKET_EDGES, jnp.float32)
    idx = jnp.sum(ages[..., None] > edges, axis=-1)
    k = len(AOU_BUCKET_EDGES) + 1
    one_hot = (idx[..., None] == jnp.arange(k)).astype(jnp.int32)
    return jnp.sum(one_hot, axis=-2)


def schedule_diag(out: EngineSchedule, ages=None, *, cell=None,
                  n_cells: int = 1) -> dict:
    """Per-round diagnostics of an ``EngineSchedule`` — jax twin of
    ``plan.schedule_diag`` with a leading batch dim on every leaf
    (parity-tested leaf-for-leaf; jittable — pure jnp ops on fixed
    shapes). Leaves: t_round/t_comp_bottleneck/t_up_bottleneck (B,) f32,
    n_selected/n_evicted (B,) int32, plus aou_hist (B, 7) int32 when
    ``ages`` is given and sel_per_cell (B, n_cells) int32 when a cell map
    is given. The numpy-only ``joint_swaps_accepted`` leaf has no jax twin
    (the engine's joint refinement is branch-free; DESIGN.md section 11).
    """
    sel = out.selected
    tot = jnp.where(sel, out.t_cmp + out.t_com, 0.0)
    bi = jnp.argmax(tot, axis=-1)
    any_sel = jnp.any(sel, axis=-1)
    take = lambda a: jnp.where(
        any_sel, jnp.take_along_axis(a, bi[..., None], axis=-1)[..., 0], 0.0)
    diag = {
        "t_round": out.t_round,
        "t_comp_bottleneck": take(out.t_cmp),
        "t_up_bottleneck": take(out.t_com),
        "n_selected": jnp.sum(sel, axis=-1).astype(jnp.int32),
        "n_evicted": jnp.sum(out.evicted, axis=-1).astype(jnp.int32),
    }
    if ages is not None:
        diag["aou_hist"] = _aou_histogram(jnp.asarray(ages, jnp.float32))
    if cell is not None and n_cells > 1:
        one_hot = (jnp.asarray(cell)[..., None]
                   == jnp.arange(n_cells)).astype(jnp.int32)
        diag["sel_per_cell"] = jnp.sum(
            jnp.where(sel[..., None], one_hot, 0), axis=-2)
    return diag


# ---------------------------------------------------------------------------
# sorting primitives
#
# XLA's CPU sort is comparator-driven and ~40us/row for (512, 256) — it
# dominates the whole schedule. These bitonic networks are pure
# reshape/where passes that vectorize across the batch (~8x faster on CPU,
# MXU/VPU-friendly on TPU). DESIGN.md section 5.3.
# ---------------------------------------------------------------------------


def _bitonic_sort_desc(keys):
    """Descending sort of ``keys`` along the last axis, values only.
    Pads to a power of two with -inf / INT_MIN (sinks to the end)."""
    orig = keys.shape[-1]
    m = max(2, 1 << max(orig - 1, 0).bit_length())
    batch = keys.shape[:-1]
    if m != orig:
        pad = (-jnp.inf if jnp.issubdtype(keys.dtype, jnp.floating)
               else jnp.iinfo(keys.dtype).min)
        keys = jnp.pad(keys, [(0, 0)] * len(batch) + [(0, m - orig)],
                       constant_values=pad)
    pos = jnp.arange(m, dtype=jnp.int32)
    k = 2
    while k <= m:
        j = k // 2
        while j >= 1:
            kk = keys.reshape(*batch, m // (2 * j), 2, j)
            a, b = kk[..., 0, :], kk[..., 1, :]
            desc = (pos.reshape(m // (2 * j), 2, j)[:, 0, :] & k) == 0
            lo = jnp.where(desc, jnp.maximum(a, b), jnp.minimum(a, b))
            hi = jnp.where(desc, jnp.minimum(a, b), jnp.maximum(a, b))
            keys = jnp.concatenate([lo[..., None, :], hi[..., None, :]],
                                   -2).reshape(*batch, m)
            j //= 2
        k *= 2
    return keys[..., :orig]


def _bitonic_argsort_desc(keys):
    """Descending argsort: returns (sorted_keys, indices). Equal keys are
    ordered by index (== numpy's stable descending argsort). Key and index
    planes ride one fused (…, 2, n) tensor so each stage is a single
    concatenate."""
    orig = keys.shape[-1]
    m = max(2, 1 << max(orig - 1, 0).bit_length())
    batch = keys.shape[:-1]
    if m != orig:
        keys = jnp.pad(keys, [(0, 0)] * len(batch) + [(0, m - orig)],
                       constant_values=-jnp.inf)
    idx = jnp.broadcast_to(
        jnp.arange(m, dtype=keys.dtype), keys.shape)
    fused = jnp.stack([keys, idx], axis=-2)          # (..., 2, m)
    pos = jnp.arange(m, dtype=jnp.int32)
    k = 2
    while k <= m:
        j = k // 2
        while j >= 1:
            kk = fused.reshape(*batch, 2, m // (2 * j), 2, j)
            a, b = kk[..., 0, :], kk[..., 1, :]      # (..., 2, blocks, j)
            ak, ai = a[..., 0, :, :], a[..., 1, :, :]
            bk, bi = b[..., 0, :, :], b[..., 1, :, :]
            desc = (pos.reshape(m // (2 * j), 2, j)[:, 0, :] & k) == 0
            a_first = (ak > bk) | ((ak == bk) & (ai < bi))
            swap = jnp.where(desc, ~a_first, a_first)[..., None, :, :]
            na = jnp.where(swap, b, a)
            nb = jnp.where(swap, a, b)
            fused = jnp.concatenate([na[..., None, :], nb[..., None, :]],
                                    -2).reshape(*batch, 2, m)
            j //= 2
        k *= 2
    return fused[..., 0, :orig], fused[..., 1, :orig].astype(jnp.int32)


def _lower_bound(a, targets, lo=None, hi=None, width=None):
    """For each (batch, t): smallest position p with a[..., p] >= t, over a
    non-decreasing int array ``a``. Vectorized binary search (gathers only).
    Optional per-query [lo, hi] bounds (with static interval ``width``)
    shrink the iteration count.
    """
    n = a.shape[-1]
    if lo is None:
        lo = jnp.zeros(targets.shape, jnp.int32)
        hi = jnp.full(targets.shape, n, jnp.int32)
        width = n
    steps = int(width).bit_length()   # interval is [lo, lo+width] inclusive
    for _ in range(steps):
        mid = (lo + hi) // 2
        amid = jnp.take_along_axis(a, jnp.clip(mid, 0, n - 1), axis=-1)
        pred = amid < targets
        lo = jnp.where(pred, mid + 1, lo)
        hi = jnp.where(pred, hi, mid)
    return lo


def _kth_of_two_sorted_desc(a, b, k):
    """Exact k-th largest (1-based) of the union of two descending-sorted
    rows ``a`` (…, na) and ``b`` (…, nb): merge-path binary search on tiny
    (…, 1) queries instead of sorting the concatenation. ``k`` is a static
    int or a traced (…, 1) int array (per-batch query — the selection
    tiebreak's need-th-largest-gain pass)."""
    na, nb = a.shape[-1], b.shape[-1]
    inf = jnp.inf
    k = jnp.asarray(k, jnp.int32)
    shp = a.shape[:-1] + (1,)
    lo = jnp.broadcast_to(jnp.maximum(0, k - nb), shp).astype(jnp.int32)
    hi = jnp.broadcast_to(jnp.minimum(k, na), shp).astype(jnp.int32)
    for _ in range(int(max(na, 1)).bit_length() + 1):
        t = (lo + hi) // 2           # take t from a, k - t from b
        a_t = jnp.take_along_axis(a, jnp.clip(t, 0, na - 1), axis=-1)
        b_prev = jnp.take_along_axis(b, jnp.clip(k - t - 1, 0, nb - 1),
                                     axis=-1)
        # can we take one more from a? (a[t] is the next a-element)
        more_a = (t < jnp.minimum(k, na)) & (
            (k - t <= 0) | (a_t >= b_prev))
        lo = jnp.where(more_a, t + 1, lo)
        hi = jnp.where(more_a, hi, t)
    t = lo
    a_last = jnp.where(t > 0, jnp.take_along_axis(
        a, jnp.clip(t - 1, 0, na - 1), axis=-1), inf)
    b_last = jnp.where(k - t > 0, jnp.take_along_axis(
        b, jnp.clip(k - t - 1, 0, nb - 1), axis=-1), inf)
    return jnp.minimum(a_last, b_last)


def _lex_rank_desc(sorted_keys, sorted_idx, keys, idx):
    """Position of each (key, idx) pair in the (descending key, ascending
    idx) lexicographic order given by (sorted_keys, sorted_idx) — the exact
    inverse of ``_bitonic_argsort_desc`` computed with gathers only."""
    n = sorted_keys.shape[-1]
    steps = n.bit_length()        # search interval is [0, n] inclusive
    lo = jnp.zeros(keys.shape, jnp.int32)
    hi = jnp.full(keys.shape, n, jnp.int32)
    for _ in range(steps):
        mid = (lo + hi) // 2
        midc = jnp.clip(mid, 0, n - 1)
        sk = jnp.take_along_axis(sorted_keys, midc, axis=-1)
        si = jnp.take_along_axis(sorted_idx, midc, axis=-1)
        before = (sk > keys) | ((sk == keys) & (si < idx))
        lo = jnp.where(before, mid + 1, lo)
        hi = jnp.where(before, hi, mid)
    return lo


# ---------------------------------------------------------------------------
# shared stage twins: completion tables + joint (pairing-aware) admission
#
# These transcribe the core/plan.py stage contract (DESIGN.md section 8):
# the subset/matching enumeration orders, the swap/prune schedule, and the
# never-worse guard are IMPORTED from plan.py so the fp64 reference and the
# fp32 device path can never disagree on coverage or tiebreak order.
# ---------------------------------------------------------------------------


def _completion_table(g_sorted, t_cmp_sorted, model_bits, prm: EngineParams,
                      oma: bool, impl: str = "xla"):
    """``pairscore.completion_table`` with the engine's static params —
    the ONE rate-table construction shared by the fast path's matching
    solve, the budget core, and the joint admission search (rate-table
    reuse; numpy twin: ``pairing.completion_table``). Non-xla ``impl``
    routes to the fused planner kernel's bf16 tiles upcast to fp32
    (DESIGN.md section 13)."""
    return pairscore.completion_table(
        g_sorted, t_cmp_sorted, model_bits, n0b=prm.noise_power_w,
        pmax=prm.max_power_w, bw=prm.bandwidth_hz, oma=oma, impl=impl)


def _sw_completion(mask, gains, t_cmp, model_bits, prm: EngineParams,
                   oma: bool, c: int, segmented: bool = False):
    """Strong_weak completion of the ``c``-member sets in ``mask``
    (jax twin of ``plan.sw_completion``): returns (t_round (B,),
    per-rank completions (B, c), member client ids by rank (B, c)).

    ``segmented=True`` (the segmented admission path, requires exactly
    ``c`` members per row and c < n) compacts the mask to (B, c) first and
    argsorts only that — identical results (``comp`` ascends in client
    index, so slot-stable == index-stable), without the (B, n) sort."""
    n0b, pmax, bw = prm.noise_power_w, prm.max_power_w, prm.bandwidth_hz
    if segmented:
        b, n = gains.shape
        cposc = jnp.cumsum(mask.astype(jnp.int32), axis=1)
        targets = jnp.broadcast_to(
            jnp.arange(1, c + 1, dtype=jnp.int32), (b, c))
        span = jnp.arange(c, dtype=jnp.int32)
        comp = _lower_bound(cposc, targets,
                            lo=jnp.broadcast_to(span, (b, c)),
                            hi=jnp.broadcast_to(span + (n - c), (b, c)),
                            width=n - c)
        sg, sidx_c = _bitonic_argsort_desc(
            jnp.take_along_axis(gains, comp, axis=1))
        sidx = jnp.take_along_axis(comp, sidx_c, axis=1)
    else:
        sg, sidx = _bitonic_argsort_desc(jnp.where(mask, gains, -jnp.inf))
        sg, sidx = sg[:, :c], sidx[:, :c]
    tc = jnp.take_along_axis(t_cmp, sidx, axis=1)
    odd = c % 2
    cp = c - odd
    m = cp // 2
    mb = model_bits[:, None]
    parts = []
    if m:
        g_wk = jnp.flip(sg[:, m:cp], axis=1)       # rank cp-1-p pairs rank p
        _, _, r_i, r_j = pairscore._pair_math(sg[:, :m], g_wk, n0b=n0b,
                                              pmax=pmax, bw=bw, oma=oma)
        comp_s = tc[:, :m] + mb / jnp.maximum(r_i, 1e-9)
        comp_w = jnp.flip(tc[:, m:cp], axis=1) + mb / jnp.maximum(r_j, 1e-9)
        parts = [comp_s, jnp.flip(comp_w, axis=1)]
    if odd:
        solo = tc[:, cp:] + mb / jnp.maximum(
            pairscore.solo_rate_math(sg[:, cp:], n0b=n0b, pmax=pmax, bw=bw),
            1e-9)
        parts.append(solo)
    comp = jnp.concatenate(parts, axis=1)
    return jnp.max(comp, axis=1), comp, sidx


def _joint_enum_mask(gains, t_cmp, model_bits, prm: EngineParams, oma: bool,
                     n: int, c: int):
    """Exhaustive joint admission (static n <= JOINT_ENUM_MAX_N): evaluate
    every C(n, c) candidate set at its optimal matching over the shared
    ``plan.enumerate_subsets`` x ``pairing.enumerate_matchings`` static
    tables, argmin-first. Solo convention: weakest member when c is odd."""
    b = gains.shape[0]
    subsets = jnp.asarray(enumerate_subsets(n, c), jnp.int32)    # (L, c)
    g_s = gains[:, subsets]                                      # (B, L, c)
    t_s = t_cmp[:, subsets]
    sg, sidx = _bitonic_argsort_desc(g_s)
    st = jnp.take_along_axis(t_s, sidx, axis=-1)
    odd = c % 2
    cp = c - odd
    m = cp // 2
    if m:
        table = _completion_table(sg[..., :cp], st[..., :cp],
                                  model_bits[:, None], prm, oma)
        mt = jnp.asarray(enumerate_matchings(m), jnp.int32)      # (M, m, 2)
        vals = table[:, :, mt[:, :, 0], mt[:, :, 1]]             # (B,L,M,m)
        t_set = jnp.min(jnp.max(vals, axis=-1), axis=-1)         # (B, L)
    else:
        t_set = jnp.zeros(g_s.shape[:2], gains.dtype)
    if odd:
        solo = st[..., c - 1] + model_bits[:, None] / jnp.maximum(
            pairscore.solo_rate_math(sg[..., c - 1], n0b=prm.noise_power_w,
                                     pmax=prm.max_power_w,
                                     bw=prm.bandwidth_hz), 1e-9)
        t_set = jnp.maximum(t_set, solo)
    members = jnp.take(subsets, jnp.argmin(t_set, axis=1), axis=0)  # (B, c)
    return (jnp.zeros((b, gains.shape[1]), bool)
            .at[jnp.arange(b)[:, None], members].set(True))


def _joint_swap_mask(cand, gains, t_cmp, model_bits, prm: EngineParams,
                     oma: bool, c: int, segmented: bool = False):
    """Swap/prune local search from the greedy admission (jax twin of
    ``plan._swap_search``): JOINT_SWAP_ITERS unrolled iterations, each
    swapping the bottleneck member for the non-member with the best solo
    completion proxy, kept only on a strict strong_weak improvement (a
    rejected swap freezes the lane — the numpy loop breaks there)."""
    b = gains.shape[0]
    rows = jnp.arange(b)
    proxy = t_cmp + model_bits[:, None] / jnp.maximum(
        pairscore.solo_rate_math(gains, n0b=prm.noise_power_w,
                                 pmax=prm.max_power_w,
                                 bw=prm.bandwidth_hz), 1e-9)
    mask = cand
    cur_t, comp, sidx = _sw_completion(mask, gains, t_cmp, model_bits, prm,
                                       oma, c, segmented)
    for _ in range(JOINT_SWAP_ITERS):
        bneck = jnp.take_along_axis(sidx, jnp.argmax(comp, axis=1)[:, None],
                                    axis=1)[:, 0]
        incoming = jnp.argmin(jnp.where(mask, jnp.inf, proxy), axis=1)
        new_mask = (mask.at[rows, bneck].set(False)
                    .at[rows, incoming].set(True))
        new_t, new_comp, new_sidx = _sw_completion(
            new_mask, gains, t_cmp, model_bits, prm, oma, c, segmented)
        imp = new_t < cur_t
        mask = jnp.where(imp[:, None], new_mask, mask)
        comp = jnp.where(imp[:, None], new_comp, comp)
        sidx = jnp.where(imp[:, None], new_sidx, sidx)
        cur_t = jnp.where(imp, new_t, cur_t)
    return mask


def _joint_refine_mask(cand, gains, t_cmp, model_bits, prm: EngineParams,
                       oma: bool, n_cand0: int, segmented: bool = False):
    """Joint (pairing-aware) admission twin of ``plan.joint_admission`` —
    WITHOUT the realized-time guard: callers evaluate both masks through
    the shared finish stage and keep the strictly faster schedule
    (``_pick_faster``), which is exactly the plan.py guard.
    ``segmented`` routes the swap search's set evaluations through the
    compacted ``_sw_completion`` (no full-population sorts)."""
    n = gains.shape[-1]
    if n_cand0 < 1 or n_cand0 >= n:
        return cand
    if n <= JOINT_ENUM_MAX_N:
        return _joint_enum_mask(gains, t_cmp, model_bits, prm, oma, n,
                                n_cand0)
    return _joint_swap_mask(cand, gains, t_cmp, model_bits, prm, oma,
                            n_cand0, segmented)


def _pick_faster(a: EngineSchedule, b: EngineSchedule) -> EngineSchedule:
    """Per-batch-element never-worse guard: ``a`` where strictly faster,
    else ``b`` (ties keep ``b`` — the greedy set, matching plan.py)."""
    better = a.t_round < b.t_round
    return jax.tree.map(
        lambda x, y: jnp.where(
            better.reshape(better.shape + (1,) * (x.ndim - 1)), x, y),
        a, b)


# ---------------------------------------------------------------------------
# fast batched path (no round-time budget)
#
# With no budget the eviction loop never runs and the schedule admits
# exactly n_cand0 = min(slots, N) clients — a STATIC count. Selection
# reduces to a threshold compare against the n_cand0-th largest priority,
# pairing runs on the compacted (B, n_cand0) candidate arrays, and every
# client-space output is produced by gathers (XLA CPU scatter is ~50x
# slower than gather, so the path is scatter-free). DESIGN.md section 5.3.
# ---------------------------------------------------------------------------


def _admit_fast(priority, gains, n_cand0: int):
    """Stage-2 twin (greedy_set, static count): top-``n_cand0`` admission
    mask by (priority desc, gain desc, index asc) — the ``plan.
    admission_order`` tiebreak as threshold compares, no full argsort."""
    b, n = gains.shape
    c = n_cand0
    # threshold = c-th largest priority; sorting two halves simultaneously
    # (28 vs 36 bitonic stages at n=256) + a merge-path k-th query is
    # cheaper than one full-width sort
    if n % 2 == 0 and c > 1:
        halves = _bitonic_sort_desc(priority.reshape(b, 2, n // 2))
        thr = _kth_of_two_sorted_desc(halves[:, 0], halves[:, 1], c)
    else:
        thr = _bitonic_sort_desc(priority)[:, c - 1:c]
    gt = priority > thr
    eq = priority == thr
    n_gt = jnp.sum(gt, axis=1, keepdims=True)
    # ties at the threshold priority resolve by gain (then client index):
    # a second threshold pass over the tied clients' gains — the exact
    # analogue of the numpy lexsort (scheduler.schedule_age_noma). Same
    # two-half sort + merge-path k-th trick as the priority threshold
    # (need >= 1 always: at most c-1 priorities exceed the c-th largest)
    need = c - n_gt                                      # tied admissions
    g_eq = jnp.where(eq, gains, -jnp.inf)
    if n % 2 == 0 and c > 1:
        g_halves = _bitonic_sort_desc(g_eq.reshape(b, 2, n // 2))
        gthr = _kth_of_two_sorted_desc(g_halves[:, 0], g_halves[:, 1],
                                       need)
    else:
        gthr = jnp.take_along_axis(_bitonic_sort_desc(g_eq),
                                   jnp.clip(need - 1, 0, n - 1), axis=1)
    ggt = eq & (gains > gthr)
    geq = eq & (gains == gthr)
    n_ggt = jnp.sum(ggt, axis=1, keepdims=True)
    geq_rank = jnp.cumsum(geq.astype(jnp.int32), axis=1)  # 1-based ties
    return gt | ggt | (geq & (geq_rank <= need - n_ggt))  # exactly c


# ---------------------------------------------------------------------------
# segmented admission (FLConfig.admission = "segmented")
#
# The full_sort admission above still sorts the whole population (two
# n/2-wide bitonic halves), so its cost grows n log^2 n while the answer
# only needs the c-th largest priority. The segmented path finds that
# threshold EXACTLY by binary search in uint32 bit space: the IEEE-754
# order-preserving float->uint bijection makes "count(priority >= mid)"
# monotone in mid, so 32 compare+popcount passes (each a cheap O(n)
# elementwise reduction that XLA fuses) pin the exact c-th largest value —
# no slack, no refine loop, no approximation. Ties at the threshold resolve
# by the same second gains pass as full_sort, so the admitted set is
# bit-for-bit the (priority desc, gain desc, index asc) top-c of
# ``plan.admission_order``. DESIGN.md section 9.
# ---------------------------------------------------------------------------

# target rows*clients per scan sub-chunk on the segmented path: the O(n)
# count passes are memory-bound, so walking the batch in ~L2-sized slices
# inside one jitted lax.scan roughly doubles throughput at n=1000 vs one
# flat (256, n) chunk (measured; DESIGN.md section 9.3)
ADMISSION_SCAN_ELEMS = 32768


def _f2u(x):
    """Order-preserving fp32 -> uint32 bijection: flip the sign bit on
    non-negatives, all bits on negatives. ``x + 0.0`` canonicalizes -0.0 to
    +0.0 first so uint order matches float total order on every input."""
    x = x + 0.0
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(b < 0, jnp.invert(b),
                     b ^ jnp.int32(-2147483648)).astype(jnp.uint32)


def _kth_largest_u32(s, k):
    """Exact per-row k-th largest of uint32 ``s`` (…, n) by bit-space binary
    search; ``k`` is a static int or traced (…, 1) int32 (the tied-gain pass
    queries a different k per row). 32 fused count passes, no sort."""
    shp = s.shape[:-1] + (1,)
    k = jnp.broadcast_to(jnp.asarray(k, jnp.int32), shp)
    lo = jnp.zeros(shp, jnp.uint32)
    hi = jnp.full(shp, 0xFFFFFFFF, jnp.uint32)
    for _ in range(32):
        d = hi - lo
        mid = lo + d // 2 + (d & 1)      # upper mid: lo can sit at the answer
        cnt = jnp.sum((s >= mid).astype(jnp.int32), -1, keepdims=True)
        ge = cnt >= k
        lo = jnp.where(ge, mid, lo)
        hi = jnp.where(ge, hi, mid - 1)
    return lo


def _admit_fast_seg(priority, gains, n_cand0: int):
    """Segmented twin of ``_admit_fast``: identical admitted mask (the same
    lexicographic tiebreak contract), but the two thresholds come from
    ``_kth_largest_u32`` bit-space searches instead of population sorts —
    O(n) per pass, so the admission cost stops growing with sort depth.
    The gains tiebreak pass is skipped entirely (``lax.cond``) in the
    almost-sure case where no tie straddles the threshold."""
    b, n = gains.shape
    c = n_cand0
    if c >= n:
        return jnp.ones((b, n), bool)
    su = _f2u(priority)
    thr = _kth_largest_u32(su, c)
    gt = su > thr
    eq = su == thr
    n_gt = jnp.sum(gt, axis=1, keepdims=True)
    need = c - n_gt                       # >= 1: at most c-1 exceed the kth
    n_eq = jnp.sum(eq, axis=1, keepdims=True)

    def no_ties(_):
        # exactly ``need`` clients sit at the threshold in every row: the
        # admitted set is closed under priority equality, no gain pass
        return gt | eq

    def with_ties(_):
        # ties straddle the threshold somewhere: rank the tied clients'
        # gains by a second bit-space search (excluded rows get key 0 —
        # strictly below any real _f2u image of a positive gain), then
        # index ascending via cumsum over the residual exact gain ties
        gu = jnp.where(eq, _f2u(gains), jnp.uint32(0))
        gthr = _kth_largest_u32(gu, need)
        ggt = eq & (gu > gthr)
        geq = eq & (gu == gthr)
        n_ggt = jnp.sum(ggt, axis=1, keepdims=True)
        geq_rank = jnp.cumsum(geq.astype(jnp.int32), axis=1)
        return gt | ggt | (geq & (geq_rank <= need - n_ggt))

    return jax.lax.cond(jnp.all(n_eq == need), no_ties, with_ties, None)


def _fast_finish(cand, gains, t_cmp, n_samples, model_bits,
                 prm: EngineParams, oma: bool, n_pairs: int,
                 n_cand0: int, pairing_policy: str = "strong_weak",
                 impl: str = "xla") -> EngineSchedule:
    """Stages 3-5 for a static-count admission mask ``cand``: compaction,
    pairing under the policy, power/rates, round time, client-space
    gathers.

    ``impl`` (static, kernels/backend.py axis) routes the scoring and the
    matching policies' completion table through the Pallas kernels: pair
    power/rate scoring via ``pairscore.pairscore_pallas`` and the table +
    strong_weak bottleneck via the fused planner kernel
    (``kernels/planner.py``) — replacing the post-hoc rescore pass the
    engine used before. ``"xla"`` is the pure-jnp twin, bit-identical to
    the previous behavior."""
    b, n = gains.shape
    n0b, pmax, bw = prm.noise_power_w, prm.max_power_w, prm.bandwidth_hz
    c = n_cand0
    odd = c % 2
    c_pair = c - odd
    m = c_pair // 2

    # --- compaction to (B, c) in client order (monotone cumsum + search) --
    cposc = jnp.cumsum(cand.astype(jnp.int32), axis=1)   # 1..c
    targets = jnp.broadcast_to(jnp.arange(1, c + 1, dtype=jnp.int32),
                               (b, c))
    # the s-th candidate lives at client index in [s, s + n - c]
    span = jnp.arange(c, dtype=jnp.int32)
    comp = _lower_bound(cposc, targets,
                        lo=jnp.broadcast_to(span, (b, c)),
                        hi=jnp.broadcast_to(span + (n - c), (b, c)),
                        width=n - c)                     # candidate ids
    g_c = jnp.take_along_axis(gains, comp, axis=1)

    # --- candidate ordering: values-only descending gain sort, then each
    # slot's rank q by a short binary search into the sorted row. The
    # 1-plane sort is ~2x cheaper than the fused 2-plane argsort; exact
    # gain ties (measure-zero under continuous fading) would make the
    # rank search ambiguous, so a lax.cond falls back to the argsort
    # inverse (stable by slot == by client index, the plan.py contract)
    # only when some row of the chunk actually has a tie ------------------
    sg_c = _bitonic_sort_desc(g_c)

    def _distinct_q(_):
        lo = jnp.zeros((b, c), jnp.int32)
        hi = jnp.full((b, c), c, jnp.int32)
        for _ in range(int(c).bit_length()):
            mid = (lo + hi) // 2
            v = jnp.take_along_axis(sg_c, jnp.clip(mid, 0, c - 1), axis=1)
            gtm = v > g_c
            lo = jnp.where(gtm, mid + 1, lo)
            hi = jnp.where(gtm, hi, mid)
        return lo

    def _tied_q(_):
        _, sidx_c = _bitonic_argsort_desc(g_c)
        # permutation inverse via one packed-int sort: (slot << bits | rank)
        # ascending in slot leaves each slot's rank in the low bits
        mbits = max(c - 1, 1).bit_length()
        rank = jnp.arange(c, dtype=jnp.int32)
        packed = (sidx_c << mbits) | rank
        return (-_bitonic_sort_desc(-packed)) & ((1 << mbits) - 1)

    if c > 1:
        ties = jnp.any(sg_c[:, :-1] == sg_c[:, 1:])
        q = jax.lax.cond(ties, _tied_q, _distinct_q, None)
    else:
        q = jnp.zeros((b, c), jnp.int32)

    # client id by rank (the pair tables' payload): invert q with one more
    # packed-int sort — (rank << bits | client id) ascending in rank. Falls
    # back to the fused argsort when the packing would overflow int31
    # (c and N both huge; never at the paper's slot counts)
    pbits = max(n - 1, 1).bit_length()
    if ((c - 1) << pbits) | (n - 1) < 2 ** 31:
        packed2 = (q << pbits) | comp
        sid_c = (-_bitonic_sort_desc(-packed2)) & ((1 << pbits) - 1)
    else:
        _, sidx_c = _bitonic_argsort_desc(g_c)
        sid_c = jnp.take_along_axis(comp, sidx_c, axis=1)

    # --- rates/powers in SORTED space under the pairing policy (DESIGN.md
    # section 7). strong_weak keeps the original pure-slice construction
    # (rank p pairs with rank c_pair-1-p, half-width pair math); adjacent
    # is a stride-2 reshape; the matching policies solve an m x m
    # assignment of the weak half to the strong half over the pair score /
    # completion-time tables, then invert the resulting permutation with
    # one (short) bitonic argsort ------------------------------------------
    if pairing_policy == "strong_weak" or m == 0:
        g_str = sg_c[:, :m]
        g_wk = jnp.flip(sg_c[:, m:c_pair], axis=1)
        p_i, p_j, r_i, r_j = pairscore.pair_alloc_rates(
            g_str, g_wk, n0b=n0b, pmax=pmax, bw=bw, oma=oma, impl=impl)
        rate_srt = jnp.concatenate([r_i, jnp.flip(r_j, axis=1)], axis=1)
        pow_srt = jnp.concatenate([p_i, jnp.flip(p_j, axis=1)], axis=1)
        strong_tab = sid_c[:, :m]
        weak_tab = jnp.flip(sid_c[:, m:c_pair], axis=1)
    elif pairing_policy == "adjacent":
        g_str = sg_c[:, 0:c_pair:2]
        g_wk = sg_c[:, 1:c_pair:2]
        p_i, p_j, r_i, r_j = pairscore.pair_alloc_rates(
            g_str, g_wk, n0b=n0b, pmax=pmax, bw=bw, oma=oma, impl=impl)
        rate_srt = jnp.stack([r_i, r_j], axis=-1).reshape(b, c_pair)
        pow_srt = jnp.stack([p_i, p_j], axis=-1).reshape(b, c_pair)
        strong_tab = sid_c[:, 0:c_pair:2]
        weak_tab = sid_c[:, 1:c_pair:2]
    elif pairing_policy in ("hungarian", "greedy_matching"):
        ar_m = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32), (b, m))
        if pairing_policy == "greedy_matching":
            # effective-power surrogate: precision-exact structural ties
            # (greedy's argmax must break them like the fp64 reference)
            score = pairscore.effective_power_table(
                sg_c[:, :m], sg_c[:, m:c_pair], n0b=n0b, pmax=pmax)
            strong_pos = ar_m
            weak_pos = m + matching.greedy_assignment(score)
        else:
            # full sorted-rank completion table: the [0:m, m:] half-split
            # slice is the assignment cost, the whole table feeds the
            # bottleneck 2-opt + the never-slower guard (DESIGN.md 7.2).
            # Non-xla impls get the fused planner kernel's bf16 tiles
            # (upcast fp32) plus the in-kernel fp32 strong_weak bottleneck
            # t_sw, saving the separate guard gather/reduction pass.
            t_cmp_srt = jnp.take_along_axis(t_cmp, sid_c, axis=1)
            if impl == "xla":
                table = _completion_table(sg_c[:, :c_pair],
                                          t_cmp_srt[:, :c_pair], model_bits,
                                          prm, oma)
                t_sw = None
            else:
                table_t, _, t_sw = planner.planner_tables(
                    sg_c[:, :c_pair], t_cmp_srt[:, :c_pair], model_bits,
                    n0b=n0b, pmax=pmax, bw=bw, oma=oma, impl=impl)
                table = table_t.astype(jnp.float32)
            rev = jnp.broadcast_to(
                jnp.arange(c_pair - 1, m - 1, -1, dtype=jnp.int32), (b, m))
            if m <= ENUM_MAX_PAIRS:
                # exact bottleneck by enumeration (L = 1/3/15/105)
                mt = jnp.asarray(enumerate_matchings(m), jnp.int32)
                vals = table[:, mt[:, :, 0], mt[:, :, 1]]     # (B, L, m)
                best = jnp.argmin(jnp.max(vals, axis=2), axis=1)
                a_p = jnp.take(mt[:, :, 0], best, axis=0)
                b_p = jnp.take(mt[:, :, 1], best, axis=0)
            else:
                # min-sum assignment init + multi-start bottleneck 2-opt
                sigma = matching.hungarian_assignment(
                    table[:, :m, m:c_pair])
                adj = jnp.broadcast_to(
                    2 * jnp.arange(m, dtype=jnp.int32), (b, m))
                a_p, b_p = matching.best_bottleneck_matching(
                    table, ((ar_m, m + sigma), (ar_m, rev),
                            (adj, adj + 1)))
            # never-slower guard vs strong_weak (fp32 threshold math: the
            # fused kernel reduces t_sw from the pre-bf16 fp32 values)
            sw_bneck = (matching.pair_bottleneck(table, ar_m, rev)
                        if t_sw is None else t_sw)
            use = (matching.pair_bottleneck(table, a_p, b_p)
                   < sw_bneck)[:, None]
            strong_pos = jnp.where(use, a_p, ar_m)
            weak_pos = jnp.where(use, b_p, rev)
        g_str = jnp.take_along_axis(sg_c, strong_pos, axis=1)
        g_wk = jnp.take_along_axis(sg_c, weak_pos, axis=1)
        p_i, p_j, r_i, r_j = pairscore.pair_alloc_rates(
            g_str, g_wk, n0b=n0b, pmax=pmax, bw=bw, oma=oma, impl=impl)
        # sorted-space inverse of [strong_pos | weak_pos] (a permutation of
        # 0..c_pair-1): one short bitonic argsort ascending
        pos = jnp.concatenate([strong_pos, weak_pos], axis=1)
        _, inv = _bitonic_argsort_desc(-pos.astype(jnp.float32))
        rate_srt = jnp.take_along_axis(
            jnp.concatenate([r_i, r_j], axis=1), inv, axis=1)
        pow_srt = jnp.take_along_axis(
            jnp.concatenate([p_i, p_j], axis=1), inv, axis=1)
        strong_tab = jnp.take_along_axis(sid_c, strong_pos, axis=1)
        weak_tab = jnp.take_along_axis(sid_c, weak_pos, axis=1)
    else:
        raise ValueError(f"unknown pairing policy {pairing_policy!r} "
                         f"(expected one of {PAIRINGS})")
    if odd:
        solo_r = pairscore.solo_rate_math(sg_c[:, c - 1:c], n0b=n0b,
                                          pmax=pmax, bw=bw)
        rate_srt = jnp.concatenate([rate_srt, solo_r], axis=1)
        pow_srt = jnp.concatenate(
            [pow_srt, jnp.full((b, 1), pmax, rate_srt.dtype)], axis=1)

    # --- back to candidate space: ride rate and power through the gathers
    # as ONE complex64 plane (real=rate, imag=power — exact: the parts are
    # stored fp32 verbatim), halving the gather count. Round time reduces
    # over candidate space (max is order-free), so the sorted-space t_cmp
    # gather never materializes; a consumer that only reads
    # t_round/selected — the Monte-Carlo sweep — lets XLA prune the
    # client-space slot gathers below.
    rp_srt = jax.lax.complex(rate_srt, pow_srt)
    rp_c = jnp.take_along_axis(rp_srt, q, axis=1)
    rate_c = jnp.real(rp_c)
    t_cmp_c = jnp.take_along_axis(t_cmp, comp, axis=1)
    tot_c = t_cmp_c + model_bits[:, None] / jnp.maximum(rate_c, 1e-9)
    t_round = jnp.max(tot_c, axis=1)

    # --- back to client space: one slot gather ----------------------------
    slot = jnp.clip(cposc - 1, 0, c - 1)
    rp = jnp.take_along_axis(rp_c, slot, axis=1)
    rates = jnp.where(cand, jnp.real(rp), 0.0)
    powers = jnp.where(cand, jnp.imag(rp), 0.0)
    t_com = model_bits[:, None] / jnp.maximum(rates, 1e-9)
    w = n_samples * cand
    w = w / jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1e-12)

    # --- pair table: solo row + padding on the policy's (strong, weak) ids
    if odd:
        strong_tab = jnp.concatenate([strong_tab, sid_c[:, c - 1:c]], axis=1)
        weak_tab = jnp.concatenate(
            [weak_tab, jnp.full((b, 1), -1, jnp.int32)], axis=1)
    pad = n_pairs - strong_tab.shape[1]
    if pad > 0:
        fill = jnp.full((b, pad), -1, jnp.int32)
        strong_tab = jnp.concatenate([strong_tab, fill], axis=1)
        weak_tab = jnp.concatenate([weak_tab, fill], axis=1)

    return EngineSchedule(
        selected=cand, pair_strong=strong_tab.astype(jnp.int32),
        pair_weak=weak_tab.astype(jnp.int32), rates=rates, powers=powers,
        t_cmp=t_cmp, t_com=t_com, t_round=t_round, agg_weights=w,
        evicted=jnp.zeros((b, n), bool))


def _fast_schedule_batch(priority, gains, t_cmp, n_samples, model_bits,
                         prm: EngineParams, oma: bool, n_pairs: int,
                         n_cand0: int, pairing_policy: str = "strong_weak",
                         selection: str = "greedy_set",
                         admission: str = "full_sort",
                         impl: str = "xla") -> EngineSchedule:
    """Staged fast path: greedy admission -> finish; ``selection="joint"``
    additionally refines the admitted set (``_joint_refine_mask``) and
    keeps the refined schedule only where strictly faster (the plan.py
    never-worse guard, realized under the active pairing policy).
    ``admission`` picks the resolved stage-2 implementation ("full_sort" |
    "segmented" — same mask bit-for-bit, DESIGN.md section 9). ``impl``
    routes the finish stage's scoring/table through the Pallas kernels
    (the joint refine's set-search stages stay XLA: their tables are
    c <= 8 wide and padding them to 128-lane tiles measured out ~100x
    wasteful — DESIGN.md section 13)."""
    seg = admission == "segmented"
    admit = _admit_fast_seg if seg else _admit_fast
    # named scopes: each op's name-scope path in the device trace names
    # the stage it belongs to (admission vs pairing/power/round time)
    with jax.named_scope("mc.admit"):
        cand = admit(priority, gains, n_cand0)
    with jax.named_scope("mc.finish"):
        out = _fast_finish(cand, gains, t_cmp, n_samples, model_bits, prm,
                           oma, n_pairs, n_cand0, pairing_policy, impl)
    if selection == "joint" and 0 < n_cand0 < gains.shape[-1]:
        with jax.named_scope("mc.admit"):
            refined = _joint_refine_mask(cand, gains, t_cmp, model_bits,
                                         prm, oma, n_cand0, segmented=seg)
        with jax.named_scope("mc.finish"):
            out = _pick_faster(
                _fast_finish(refined, gains, t_cmp, n_samples, model_bits,
                             prm, oma, n_pairs, n_cand0, pairing_policy,
                             impl), out)
    return out


def _seg_subchunk(b: int, n: int) -> int:
    """Rows per lax.scan sub-chunk on the segmented path (0 = no scan):
    largest divisor of ``b`` with ~ADMISSION_SCAN_ELEMS row elements, so
    the O(n) count passes stay cache-resident instead of streaming the
    whole (B, n) batch through memory once per pass."""
    target = max(1, ADMISSION_SCAN_ELEMS // max(n, 1))
    if target >= b:
        return 0
    sub = 1
    for d in range(2, target + 1):
        if b % d == 0:
            sub = d
    return sub


def _scan_subchunks(step, arrays, b: int, sub: int):
    """Run ``step(*row_chunk)`` over (b // sub)-many ``sub``-row slices of
    ``arrays`` inside one ``lax.scan``, re-flattening the stacked outputs
    (bit-identical to one flat call: every op in the step is row-wise)."""
    xs = tuple(a.reshape((b // sub, sub) + a.shape[1:]) for a in arrays)
    _, out = jax.lax.scan(lambda carry, x: (carry, step(*x)), 0, xs)
    return jax.tree.map(lambda o: o.reshape((-1,) + o.shape[2:]), out)


@functools.partial(jax.jit,
                   static_argnames=("prm", "oma", "n_pairs", "n_cand0",
                                    "pairing", "selection", "admission",
                                    "impl"))
def _fast_schedule_batch_core(priority, gains, t_cmp, n_samples, model_bits,
                              *, prm: EngineParams, oma: bool, n_pairs: int,
                              n_cand0: int, pairing: str = "strong_weak",
                              selection: str = "greedy_set",
                              admission: str = "full_sort",
                              impl: str = "xla") -> EngineSchedule:
    def step(p, g, tc, ns, mb):
        return _fast_schedule_batch(p, g, tc, ns, mb, prm, oma, n_pairs,
                                    n_cand0, pairing, selection, admission,
                                    impl)

    b, n = gains.shape
    sub = _seg_subchunk(b, n) if admission == "segmented" else 0
    if sub:
        return _scan_subchunks(
            step, (priority, gains, t_cmp, n_samples, model_bits), b, sub)
    return step(priority, gains, t_cmp, n_samples, model_bits)


def _age_priority(ages, n_samples, gains, gamma: float):
    """The paper's selection key A^gamma * w — single definition shared by
    every engine entry point (batched over any leading dims). Ties resolve
    lexicographically by gain inside the selection cores (the old
    ``+ 1e-12 * gains`` epsilon was vacuous in fp32: gains ~1e-10 made the
    increment ~1e-22, absorbed next to O(0.01-1) priorities)."""
    del gains  # tiebreak handled lexicographically by the selection cores
    w = n_samples / jnp.sum(n_samples, axis=-1, keepdims=True)
    a = ages.astype(jnp.float32)
    if gamma != 1.0:       # static: skip the pow at the paper's gamma=1
        a = a ** gamma
    return a * w


def round_robin_priority(round_idx, n: int, n_window: int):
    """(n,) priority whose top-``n_window`` set is the numpy
    ``schedule_round_robin`` rotating window ``[(t*slots + i) % n]`` —
    single definition shared by the Monte-Carlo step (traced round_idx)
    and the FLServer engine path (Python int)."""
    start = (round_idx * n_window) % n
    return -(((jnp.arange(n, dtype=jnp.int32) - start) % n)
             .astype(jnp.float32))


def _compute_times(prm: EngineParams, n_samples, cpu_freq):
    """T_cmp = E * C * D_n / f_n (``core.roundtime.compute_times``)."""
    return (prm.local_epochs * prm.cycles_per_sample * n_samples
            / cpu_freq).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("prm", "gamma", "oma",
                                             "n_pairs", "n_cand0",
                                             "pairing", "selection",
                                             "admission", "impl"))
def _fast_from_env_core(gains, n_samples, cpu_freq, ages, model_bits, *,
                        prm: EngineParams, gamma: float, oma: bool,
                        n_pairs: int, n_cand0: int,
                        pairing: str = "strong_weak",
                        selection: str = "greedy_set",
                        admission: str = "full_sort",
                        impl: str = "xla") -> EngineSchedule:
    """Age-priority preamble fused with the fast path: one dispatch per
    batch (the eager preamble otherwise costs several ms on CPU). On the
    segmented path the preamble rides inside the cache-blocked sub-chunk
    scan (every op is row-wise)."""
    def step(g, ns, cf, ag, mb):
        priority = _age_priority(ag, ns, g, gamma)
        t_cmp = _compute_times(prm, ns, cf)
        return _fast_schedule_batch(priority, g, t_cmp, ns, mb, prm, oma,
                                    n_pairs, n_cand0, pairing, selection,
                                    admission, impl)

    b, n = gains.shape
    sub = _seg_subchunk(b, n) if admission == "segmented" else 0
    if sub:
        return _scan_subchunks(
            step, (gains, n_samples, cpu_freq, ages, model_bits), b, sub)
    return step(gains, n_samples, cpu_freq, ages, model_bits)


# ---------------------------------------------------------------------------
# general single-env core (vmapped below; exact eviction loop)
# ---------------------------------------------------------------------------


def _assemble(cand, gains, t_cmp, model_bits, prm: EngineParams, oma: bool,
              n_pairs: int, pairing_policy: str = "strong_weak"):
    """Pair the candidate mask under ``pairing_policy``, allocate power,
    scatter rates/powers.

    Mirrors ``plan.match_candidates`` + ``plan.allocate_rates``: sort
    candidates by gain (descending,
    non-candidates pushed past the end with -inf keys), pair them per the
    policy (core/pairing.py is the fp64 reference); an odd count parks the
    weakest on a solo subchannel at full power. The candidate count is
    traced here (the budget-eviction loop shrinks it), so the matching
    policies run on a ``pad_cost_table``-masked static (P, P) table.
    """
    n = gains.shape[0]
    n0b, pmax, bw = prm.noise_power_w, prm.max_power_w, prm.bandwidth_hz
    c = jnp.sum(cand.astype(jnp.int32))
    sidx = jnp.argsort(-jnp.where(cand, gains, -jnp.inf))
    odd = c % 2
    has_solo = odd.astype(bool)
    c_pair = c - odd
    m = c_pair // 2
    solo_idx = sidx[jnp.clip(c - 1, 0, n - 1)]

    i = jnp.arange(n_pairs)
    valid = i < m
    if pairing_policy == "strong_weak":
        strong_at = i
        weak_at = c_pair - 1 - i
    elif pairing_policy == "adjacent":
        strong_at = 2 * i
        weak_at = 2 * i + 1
    elif pairing_policy == "greedy_matching":
        g_s = gains[sidx[jnp.clip(i, 0, n - 1)]]           # strong half
        g_w = gains[sidx[jnp.clip(m + i, 0, n - 1)]]       # weak half
        score = jnp.where(valid[:, None] & valid[None, :],
                          pairscore.effective_power_table(
                              g_s, g_w, n0b=n0b, pmax=pmax), -1.0)
        sigma = matching.greedy_assignment(score)
        strong_at = i
        weak_at = m + sigma
    elif pairing_policy == "hungarian":
        # full sorted-rank completion table at static size s2 (traced
        # candidate count m; the [0:P, m:] slice is the assignment cost)
        s2 = min(2 * n_pairs, n)
        r2 = jnp.clip(jnp.arange(s2), 0, n - 1)
        g_all = gains[sidx[r2]]
        tc_all = t_cmp[sidx[r2]]
        table = _completion_table(g_all, tc_all, model_bits, prm, oma)
        ii = i.astype(jnp.int32)
        rev = jnp.where(valid, c_pair - 1 - i, i).astype(jnp.int32)

        # exact bottleneck enumeration lanes for tiny traced pair counts
        # (the numpy reference applies the same runtime
        # m <= ENUM_MAX_PAIRS rule)
        a_p, b_p = ii, rev
        for mm in range(1, min(ENUM_MAX_PAIRS, n_pairs) + 1):
            if 2 * mm > s2:
                continue
            mt = jnp.asarray(enumerate_matchings(mm), jnp.int32)
            vals = table[mt[:, :, 0], mt[:, :, 1]]           # (L, mm)
            best = jnp.argmin(jnp.max(vals, axis=1))
            am = jnp.concatenate(
                [jnp.take(mt[:, :, 0], best, axis=0), ii[mm:]])
            bm = jnp.concatenate(
                [jnp.take(mt[:, :, 1], best, axis=0), ii[mm:]])
            a_p = jnp.where(m == mm, am, a_p)
            b_p = jnp.where(m == mm, bm, b_p)
        if n_pairs > ENUM_MAX_PAIRS:
            # larger instances: min-sum assignment + multi-start 2-opt
            # (the same matching.best_bottleneck_matching pipeline the
            # fast path runs, masked for the traced pair count)
            cost = table[:n_pairs][:, jnp.clip(m + i, 0, s2 - 1)]
            sigma = matching.hungarian_assignment(
                matching.pad_cost_table(cost, m))
            adj = 2 * ii
            ah, bh = matching.best_bottleneck_matching(
                table, ((ii, (m + sigma).astype(jnp.int32)), (ii, rev),
                        (adj, adj + 1)), m_valid=m)
            big = m > ENUM_MAX_PAIRS
            a_p = jnp.where(big, ah, a_p)
            b_p = jnp.where(big, bh, b_p)
        # never-slower guard vs strong_weak
        use = (matching.pair_bottleneck(table, a_p, b_p, m_valid=m)
               < matching.pair_bottleneck(table, ii, rev, m_valid=m))
        strong_at = jnp.where(use, a_p, i)
        weak_at = jnp.where(use, b_p, rev)
    else:
        raise ValueError(f"unknown pairing policy {pairing_policy!r} "
                         f"(expected one of {PAIRINGS})")
    strong = jnp.where(valid, sidx[jnp.clip(strong_at, 0, n - 1)], -1)
    weak = jnp.where(valid, sidx[jnp.clip(weak_at, 0, n - 1)], -1)
    g_i = gains[jnp.clip(strong, 0, n - 1)]
    g_j = gains[jnp.clip(weak, 0, n - 1)]
    p_i, p_j, r_i, r_j = pairscore._pair_math(g_i, g_j, n0b=n0b, pmax=pmax,
                                              bw=bw, oma=oma)

    # scatter with index n as the drop target for invalid rows (negative
    # indices would wrap)
    s_at = jnp.where(valid, strong, n)
    w_at = jnp.where(valid, weak, n)
    rates = jnp.zeros(n, jnp.float32)
    powers = jnp.zeros(n, jnp.float32)
    rates = rates.at[s_at].set(r_i, mode="drop").at[w_at].set(r_j,
                                                              mode="drop")
    powers = powers.at[s_at].set(p_i, mode="drop").at[w_at].set(p_j,
                                                                mode="drop")
    solo_at = jnp.where(has_solo, solo_idx, n)
    solo_r = pairscore.solo_rate_math(gains[jnp.clip(solo_idx, 0, n - 1)],
                                      n0b=n0b, pmax=pmax, bw=bw)
    rates = rates.at[solo_at].set(solo_r, mode="drop")
    powers = powers.at[solo_at].set(pmax, mode="drop")

    # the solo subchannel occupies pair row m as (solo, -1)
    m_at = jnp.clip(m, 0, n_pairs - 1)
    strong = strong.at[m_at].set(jnp.where(has_solo, solo_idx, strong[m_at]))
    return strong, weak, rates, powers


class _LoopState(NamedTuple):
    cand: jax.Array
    evicted: jax.Array
    qptr: jax.Array
    done: jax.Array
    strong: jax.Array
    weak: jax.Array
    rates: jax.Array
    powers: jax.Array
    t_com: jax.Array
    tot: jax.Array
    t_round: jax.Array


def _schedule_one(priority, gains, t_cmp, n_samples, model_bits, t_budget,
                  prm: EngineParams, oma: bool, n_pairs: int, n_cand0: int,
                  pairing: str = "strong_weak",
                  selection: str = "greedy_set"):
    """One env: top-``n_cand0`` admission by (priority, gain, index)
    lexicographic rank (plus the joint refinement + realized-time guard
    under ``selection="joint"``), then the budget eviction/backfill
    do-while (``plan.plan_round``)."""
    n = gains.shape[0]
    gains = gains.astype(jnp.float32)
    order = jnp.lexsort((jnp.arange(n), -gains, -priority))
    cand0 = jnp.zeros(n, bool).at[order[:n_cand0]].set(True)

    def sched_of(cand):
        strong, weak, rates, powers = _assemble(cand, gains, t_cmp,
                                                model_bits, prm, oma,
                                                n_pairs, pairing)
        t_com = model_bits / jnp.maximum(rates, 1e-9)
        tot = jnp.where(cand, t_cmp + t_com, 0.0)
        t_round = jnp.max(tot)
        return strong, weak, rates, powers, t_com, tot, t_round

    if selection == "joint" and 0 < n_cand0 < n:
        refined = _joint_refine_mask(
            cand0[None], gains[None], t_cmp[None],
            jnp.reshape(jnp.asarray(model_bits, jnp.float32), (1,)), prm,
            oma, n_cand0)[0]
        s_joint = sched_of(refined)
        s_greedy = sched_of(cand0)
        use = s_joint[6] < s_greedy[6]      # never-worse guard (realized)
        cand0 = jnp.where(use, refined, cand0)
        s0 = tuple(jnp.where(use, a, b) for a, b in zip(s_joint, s_greedy))
    else:
        s0 = sched_of(cand0)
    count0 = jnp.sum(cand0.astype(jnp.int32))
    done0 = (t_budget <= 0.0) | (s0[6] <= t_budget) | (count0 <= 1)
    st = _LoopState(cand0, jnp.zeros(n, bool),
                    jnp.asarray(prm.slots, jnp.int32), done0, *s0)

    def body(st: _LoopState) -> _LoopState:
        # evict the latency-critical client, backfill the first
        # never-admitted, never-evicted client at-or-after the cursor in
        # priority order (== the numpy order[slots:] re-scan; joint
        # admission can place later-order clients in cand, so the scan
        # skips them instead of trusting a bare cursor)
        worst = jnp.argmax(st.tot)
        cand = st.cand.at[worst].set(False)
        evicted = st.evicted.at[worst].set(True)
        elig = (~cand[order] & ~evicted[order]
                & (jnp.arange(n) >= st.qptr))
        fill = jnp.any(elig)
        pos = jnp.argmax(elig).astype(jnp.int32)
        nxt_at = jnp.where(fill, order[pos], n)
        cand = cand.at[nxt_at].set(True, mode="drop")
        qptr = jnp.where(fill, pos + 1, st.qptr)
        s = sched_of(cand)
        count = jnp.sum(cand.astype(jnp.int32))
        done = (s[6] <= t_budget) | (count <= 1)
        new = _LoopState(cand, evicted, qptr, done, *s)
        # freeze lanes that were already done (belt-and-braces under vmap)
        return jax.tree.map(
            lambda old, upd: jnp.where(st.done, old, upd), st, new)

    st = jax.lax.while_loop(lambda s: ~s.done, body, st)

    w = n_samples.astype(jnp.float32) * st.cand
    w = w / jnp.maximum(jnp.sum(w), 1e-12)
    return EngineSchedule(
        selected=st.cand, pair_strong=st.strong.astype(jnp.int32),
        pair_weak=st.weak.astype(jnp.int32), rates=st.rates,
        powers=st.powers, t_cmp=t_cmp, t_com=st.t_com, t_round=st.t_round,
        agg_weights=w, evicted=st.evicted)


@functools.partial(jax.jit,
                   static_argnames=("prm", "oma", "n_pairs", "n_cand0",
                                    "pairing", "selection"))
def _schedule_batch_core(priority, gains, t_cmp, n_samples, model_bits,
                         t_budget, *, prm: EngineParams, oma: bool,
                         n_pairs: int, n_cand0: int,
                         pairing: str = "strong_weak",
                         selection: str = "greedy_set") -> EngineSchedule:
    fn = functools.partial(_schedule_one, prm=prm, oma=oma, n_pairs=n_pairs,
                           n_cand0=n_cand0, pairing=pairing,
                           selection=selection)
    return jax.vmap(fn)(priority, gains, t_cmp, n_samples, model_bits,
                        t_budget)


# ---------------------------------------------------------------------------
# multi-cell core: partition clients by cell, vmap the planner over the
# (batch x cell) axis, merge back to client space (plan.plan_multicell twin)
# ---------------------------------------------------------------------------


def _cell_member_table(cell, n_cells: int, cap: int):
    """Static-shape membership table: (B, C, cap) client indices per cell
    (first ``cap`` members in client-index order — plan.py's truncation
    rule — padded with the sentinel ``n``). One sort of ``cell * n + idx``
    keys groups members contiguously; ``_lower_bound`` finds each row's
    first occurrence of its own cell id, giving the within-cell position
    without a segmented cumsum."""
    b, n = cell.shape
    key = cell.astype(jnp.int32) * n + jnp.arange(n, dtype=jnp.int32)
    skey = jnp.sort(key, axis=1)
    scell = skey // n
    sidx = skey % n
    first = _lower_bound(scell, scell)
    posc = jnp.arange(n, dtype=jnp.int32) - first.astype(jnp.int32)
    dest = jnp.where(posc < cap, scell * cap + posc, n_cells * cap)
    tbl = (jnp.full((b, n_cells * cap), n, jnp.int32)
           .at[jnp.arange(b)[:, None], dest].set(sidx, mode="drop"))
    return tbl.reshape(b, n_cells, cap)


def _multicell_schedule(priority, gains, t_cmp, n_samples, model_bits,
                        t_budget, cell, *, prm: EngineParams, oma: bool,
                        pairing: str, selection: str, admission: str,
                        n_cells: int, cap: int, budget: bool,
                        impl: str = "xla") -> EngineSchedule:
    """Cell-partitioned planner: gather each cell's (<= cap) members into
    a compact (B*C, cap) sub-batch, run the EXISTING per-cell pipeline —
    the fast path (with the segmented admission's cache-blocked scan) or
    the budget eviction loop — vmapped over the fused batch x cell axis,
    then merge per-cell outputs back to client space.

    Padding lanes carry (priority=-inf, gains=0): both admission paths
    rank them strictly last, ``_pair_math``'s g=0 guard gives them rate 0,
    and the merge drops them. With ``n_cells=1`` the member table is the
    identity, so the result is bitwise the single-cell planner's (the C=1
    equivalence contract, pinned by tests)."""
    b, n = gains.shape
    tbl = _cell_member_table(cell, n_cells, cap)
    valid = tbl < n
    tclip = jnp.minimum(tbl, n - 1)

    def gather(x, fill):
        g = jnp.take_along_axis(
            jnp.broadcast_to(x[:, None, :], (b, n_cells, n)), tclip, axis=2)
        return jnp.where(valid, g, fill).reshape(b * n_cells, cap)

    c_prio = gather(priority, -jnp.inf)
    c_g = gather(gains, 0.0)
    c_tc = gather(t_cmp, 0.0)
    c_ns = gather(n_samples, 0.0)
    c_mb = jnp.repeat(model_bits, n_cells)
    n_cand0 = min(prm.slots, cap)
    n_pairs = max((n_cand0 + 1) // 2, 1)
    if budget:
        c_tb = jnp.repeat(t_budget, n_cells)
        one = functools.partial(_schedule_one, prm=prm, oma=oma,
                                n_pairs=n_pairs, n_cand0=n_cand0,
                                pairing=pairing, selection=selection)
        sub = jax.vmap(one)(c_prio, c_g, c_tc, c_ns, c_mb, c_tb)
    else:
        def step(p, g, tc, ns, mbx):
            return _fast_schedule_batch(p, g, tc, ns, mbx, prm, oma,
                                        n_pairs, n_cand0, pairing,
                                        selection, admission, impl)

        rows = b * n_cells
        subc = _seg_subchunk(rows, cap) if admission == "segmented" else 0
        if subc:
            sub = _scan_subchunks(step, (c_prio, c_g, c_tc, c_ns, c_mb),
                                  rows, subc)
        else:
            sub = step(c_prio, c_g, c_tc, c_ns, c_mb)
    return _merge_cells(sub, tbl, valid, t_cmp, n_samples, model_bits)


def _merge_cells(sub: EngineSchedule, tbl, valid, t_cmp, n_samples,
                 model_bits) -> EngineSchedule:
    """Scatter per-cell schedules back to client space. Global round time
    = max over cells of the per-cell round time (cells transmit in
    parallel; the server waits for the slowest cell); aggregation weights
    pooled over ALL selected clients (one global FedAvg); pair tables
    remapped from within-cell to global client ids."""
    b, n_cells, cap = tbl.shape
    n = t_cmp.shape[1]
    rows = jnp.arange(b)[:, None]
    re = lambda x: x.reshape(b, n_cells, cap)
    sel_pc = re(sub.selected) & valid
    tot_pc = jnp.where(sel_pc, re(sub.t_cmp) + re(sub.t_com), 0.0)
    t_round = jnp.max(tot_pc, axis=(1, 2))
    cols = jnp.where(valid, tbl, n).reshape(b, n_cells * cap)

    def scat(v, dtype):
        flat = v.reshape(b, n_cells * cap).astype(dtype)
        return (jnp.zeros((b, n), dtype)
                .at[rows, cols].set(flat, mode="drop"))

    selected = scat(sub.selected, bool)
    rates = scat(sub.rates, jnp.float32)
    powers = scat(sub.powers, jnp.float32)
    evicted = scat(sub.evicted, bool)
    # single-cell t_com convention: mb / max(rate, 1e-9) for EVERY client
    # (bitwise equal to the per-cell values at member positions — same
    # fp32 formula on bit-identical rates)
    t_com = model_bits[:, None] / jnp.maximum(rates, 1e-9)
    # pair tables: within-cell ids -> global ids via the member table
    # ((B, C, P) gather along the cap axis); rows pointing at padding
    # members or pad rows collapse to -1
    def remap(p):
        pc = p.reshape(b, n_cells, -1)
        g = jnp.take_along_axis(tbl, jnp.clip(pc, 0, cap - 1), axis=2)
        return jnp.where((pc >= 0) & (g < n), g,
                         -1).reshape(b, -1).astype(jnp.int32)

    w = n_samples.astype(jnp.float32) * selected
    w = w / jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1e-12)
    return EngineSchedule(
        selected=selected, pair_strong=remap(sub.pair_strong),
        pair_weak=remap(sub.pair_weak), rates=rates, powers=powers,
        t_cmp=t_cmp, t_com=t_com, t_round=t_round, agg_weights=w,
        evicted=evicted)


@functools.partial(jax.jit,
                   static_argnames=("prm", "oma", "pairing", "selection",
                                    "admission", "n_cells", "cap",
                                    "budget", "impl"))
def _multicell_schedule_core(priority, gains, t_cmp, n_samples, model_bits,
                             t_budget, cell, *, prm: EngineParams,
                             oma: bool, pairing: str, selection: str,
                             admission: str, n_cells: int, cap: int,
                             budget: bool, impl: str = "xla"
                             ) -> EngineSchedule:
    return _multicell_schedule(priority, gains, t_cmp, n_samples,
                               model_bits, t_budget, cell, prm=prm, oma=oma,
                               pairing=pairing, selection=selection,
                               admission=admission, n_cells=n_cells,
                               cap=cap, budget=budget, impl=impl)


def _rescore_pallas(out: EngineSchedule, gains, model_bits, oma: bool,
                    prm: EngineParams, impl: str) -> EngineSchedule:
    """Recompute rates/powers/times from the pair tables with the fused
    Pallas kernel (same math as the XLA twin used inside the cores).
    Module-level so the Monte-Carlo step can trace it too.

    Only the BUDGET (eviction-loop) paths still use this post-hoc pass:
    their candidate scoring lives inside a vmapped ``lax.while_loop``
    where a per-iteration kernel launch measured slower than one rescore
    at the end. The fast paths score in-path via ``_fast_finish(impl=)``
    instead (DESIGN.md section 13)."""
    b, n = gains.shape
    strong, weak = out.pair_strong, out.pair_weak
    pair_valid = weak >= 0
    solo_valid = (strong >= 0) & (weak < 0)
    g_i = jnp.take_along_axis(gains, jnp.clip(strong, 0, n - 1), axis=1)
    g_j = jnp.take_along_axis(gains, jnp.clip(weak, 0, n - 1), axis=1)
    p_i, p_j, r_i, r_j = pairscore.pair_alloc_rates(
        g_i, g_j, n0b=prm.noise_power_w, pmax=prm.max_power_w,
        bw=prm.bandwidth_hz, oma=oma, impl=impl)
    rows = jnp.arange(b)[:, None]
    s_at = jnp.where(pair_valid, strong, n)
    w_at = jnp.where(pair_valid, weak, n)
    rates = jnp.zeros((b, n), jnp.float32)
    powers = jnp.zeros((b, n), jnp.float32)
    rates = rates.at[rows, s_at].set(r_i, mode="drop")
    rates = rates.at[rows, w_at].set(r_j, mode="drop")
    powers = powers.at[rows, s_at].set(p_i, mode="drop")
    powers = powers.at[rows, w_at].set(p_j, mode="drop")
    solo_at = jnp.where(solo_valid, strong, n)
    solo_r = pairscore.solo_rate_math(g_i, n0b=prm.noise_power_w,
                                      pmax=prm.max_power_w,
                                      bw=prm.bandwidth_hz)
    rates = rates.at[rows, solo_at].set(solo_r, mode="drop")
    powers = powers.at[rows, solo_at].set(prm.max_power_w, mode="drop")
    t_com = model_bits[:, None] / jnp.maximum(rates, 1e-9)
    tot = jnp.where(out.selected, out.t_cmp + t_com, 0.0)
    return out._replace(rates=rates, powers=powers, t_com=t_com,
                        t_round=jnp.max(tot, axis=1))


# ---------------------------------------------------------------------------
# engine facade
# ---------------------------------------------------------------------------


def _shard_mesh(count: int, axis: str, what: str):
    """One-axis mesh over every visible device for ``shard=True``; None on
    a one-device host (sharding is then a no-op). A ``count`` that does not
    divide evenly over the devices raises rather than running on one."""
    devs = jax.devices()
    if len(devs) == 1:
        return None
    if count % len(devs):
        raise ValueError(f"shard=True: {what}={count} does not divide over "
                         f"{len(devs)} devices")
    from jax.sharding import Mesh
    return Mesh(np.array(devs), (axis,))


def _per_shard(fn, mesh, *args):
    """``fn(*args)``; with a mesh, ``fn`` runs on each device's block of the
    leading (batch or seed) axis of every argument and output. XLA cannot
    partition the Mosaic kernels inside the planner, so each device plans
    its own rows (the rows are independent). ``check_vma`` is off because
    a ``pallas_call``'s output shapes carry no varying-axes annotation."""
    if mesh is None:
        return fn(*args)
    from jax.sharding import PartitionSpec
    spec = PartitionSpec(mesh.axis_names[0])
    return jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)(*args)


class WirelessEngine:
    """Batched scheduler with the numpy implementation's semantics.

    ``kernel_backend`` (default: ``FLConfig.kernel_backend``) picks the
    kernel lowering path (``kernels/backend.py``): ``auto`` compiles the
    Pallas kernels where the host can (Mosaic/Triton) and otherwise uses
    the XLA twins; ``pallas`` forces the kernel path (interpret fallback
    on CPU); ``pallas_interpret`` forces interpret mode. The fast path
    scores and builds its completion table in-kernel (``_fast_finish``);
    selection and the eviction loop always run in XLA.

    ``use_pallas``/``pallas_impl`` are the deprecated pre-backend spelling
    and map onto ``kernel_backend`` (use_pallas=True == "pallas";
    pallas_impl="interpret" == "pallas_interpret").
    """

    def __init__(self, ncfg: NOMAConfig, flcfg: FLConfig, *,
                 kernel_backend: Optional[str] = None,
                 use_pallas: bool = False,
                 pallas_impl: Optional[str] = None,
                 pairing: Optional[str] = None,
                 selection: Optional[str] = None,
                 admission: Optional[str] = None):
        self.ncfg = ncfg
        self.flcfg = flcfg
        self.prm = EngineParams.from_configs(ncfg, flcfg)
        self.pairing = flcfg.pairing if pairing is None else pairing
        if self.pairing not in PAIRINGS:
            raise ValueError(f"unknown pairing policy {self.pairing!r} "
                             f"(expected one of {PAIRINGS})")
        self.selection = (flcfg.selection if selection is None
                          else selection)
        if self.selection not in SELECTIONS:
            raise ValueError(f"unknown selection mode {self.selection!r} "
                             f"(expected one of {SELECTIONS})")
        self.admission = (flcfg.admission if admission is None
                          else admission)
        if self.admission not in ADMISSIONS:
            raise ValueError(f"unknown admission mode {self.admission!r} "
                             f"(expected one of {ADMISSIONS})")
        if kernel_backend is None:
            if use_pallas:
                # deprecated-arg mapping: the old default resolution
                # ("pallas" on TPU, "interpret" elsewhere) is exactly what
                # resolve_backend("pallas") does
                kernel_backend = {None: "pallas", "pallas": "pallas",
                                  "interpret": "pallas_interpret",
                                  "xla": "xla"}.get(pallas_impl)
                if kernel_backend is None:
                    raise ValueError(
                        f"unknown pallas_impl {pallas_impl!r} "
                        f"(expected one of ('xla', 'pallas', 'interpret'))")
            else:
                kernel_backend = flcfg.kernel_backend
        self.backend = resolve_backend(kernel_backend)
        self.kernel_backend = self.backend.requested
        self.impl = self.backend.impl
        self.use_pallas = self.backend.uses_pallas
        # deprecated alias some callers (benchmarks) still read
        self.pallas_impl = self.impl if self.use_pallas else None

    # -- env building ------------------------------------------------------

    def age_priority(self, ages, n_samples, gains):
        """The paper's selection key  A^gamma * w  (ties resolve by gain
        then client index inside the cores), matching
        ``schedule_age_noma``. Works batched."""
        return _age_priority(ages, n_samples, gains,
                             self.flcfg.age_exponent)

    def compute_times(self, n_samples, cpu_freq):
        """T_cmp = E * C * D_n / f_n (``core.roundtime.compute_times``)."""
        return _compute_times(self.prm, n_samples, cpu_freq)

    def sample_distances(self, key, shape):
        """Uniform-in-annulus placement (jax twin of noma.sample_distances)."""
        r2 = jax.random.uniform(key, shape,
                                minval=self.prm.min_radius_m ** 2,
                                maxval=self.prm.cell_radius_m ** 2)
        return jnp.sqrt(r2)

    def sample_gains(self, key, distances):
        """Block-fading gains rho0 * d^-kappa * Exp(1), batched over any
        leading dims of ``distances`` (jax twin of noma.sample_gains)."""
        fading = jax.random.exponential(key, distances.shape)
        return (self.prm.ref_path_loss
                * distances ** (-self.prm.path_loss_exp) * fading)

    # -- scheduling --------------------------------------------------------

    def schedule_batch(self, gains, n_samples, cpu_freq, ages, model_bits,
                       *, t_budget=0.0, oma: bool = False,
                       priority=None, shard: bool = False,
                       pairing: Optional[str] = None,
                       selection: Optional[str] = None,
                       admission: Optional[str] = None,
                       cell=None,
                       n_cells: Optional[int] = None) -> EngineSchedule:
        """Vmapped joint round over a batch of envs.

        gains/n_samples/cpu_freq/ages: (B, N); model_bits/t_budget: scalar
        or (B,). ``priority=None`` uses the paper's age priority.
        ``pairing`` overrides the engine's subchannel pairing policy
        (``FLConfig.pairing``; core/pairing.py); ``selection`` overrides
        the selection mode (``FLConfig.selection``; core/plan.py —
        ``joint`` refines the greedy set pairing-aware with a never-worse
        guard); ``admission`` overrides the admission implementation
        (``FLConfig.admission``: auto | full_sort | segmented — resolved
        per batch shape by ``plan.resolve_admission``, identical schedules
        either way).

        ``cell`` ((B, N) int serving-BS indices, ``sim`` scenario state)
        with ``n_cells > 1`` (defaults to ``FLConfig.n_cells``) routes
        through the cell-partitioned planner (``plan.plan_multicell``
        twin): each cell is planned on its own K subchannels by the same
        staged pipeline vmapped over the batch x cell axis, global round
        time = max over cells, aggregation weights pooled across cells.
        ``n_cells == 1`` ignores ``cell`` entirely (bitwise the
        single-cell path).

        When ``t_budget`` is a plain scalar <= 0 (no budget, the Monte-Carlo
        default) the admission count is static and the scatter/sort-free
        fast path runs; otherwise the exact ``lax.while_loop`` eviction
        core does.

        ``shard=True`` splits the (embarrassingly parallel) batch across
        all visible devices via jit sharding — on CPU run with
        ``XLA_FLAGS=--xla_force_host_platform_device_count=<cores>``. A
        batch that does not divide over the devices raises.
        """
        b, n = np.shape(gains)
        no_budget = (isinstance(t_budget, (int, float))
                     and float(t_budget) <= 0.0)
        sig = ("schedule_batch", b, n, no_budget, oma,
               pairing or self.pairing, selection or self.selection,
               admission or self.admission,
               (self.flcfg.n_cells if n_cells is None else n_cells)
               if cell is not None else 1,
               priority is None, self.impl)
        with trace.span("engine.schedule_batch", b=b, n=n,
                        cold=trace.cold(sig)) as sp:
            out = self._schedule_batch_impl(
                gains, n_samples, cpu_freq, ages, model_bits,
                t_budget=t_budget, oma=oma, priority=priority, shard=shard,
                pairing=pairing, selection=selection, admission=admission,
                cell=cell, n_cells=n_cells)
            sp.fence(out.t_round)
            return out

    def _schedule_batch_impl(self, gains, n_samples, cpu_freq, ages,
                             model_bits, *, t_budget=0.0, oma: bool = False,
                             priority=None, shard: bool = False,
                             pairing: Optional[str] = None,
                             selection: Optional[str] = None,
                             admission: Optional[str] = None,
                             cell=None,
                             n_cells: Optional[int] = None
                             ) -> EngineSchedule:
        gains = jnp.asarray(gains, jnp.float32)
        n_samples = jnp.asarray(n_samples, jnp.float32)
        b, n = gains.shape
        ages = jnp.asarray(ages, jnp.float32)
        model_bits = jnp.broadcast_to(
            jnp.asarray(model_bits, jnp.float32), (b,))
        n_cand0 = min(self.prm.slots, n)
        n_pairs = max((n_cand0 + 1) // 2, 1)
        mesh = _shard_mesh(b, "b", "batch") if shard else None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            sh = NamedSharding(mesh, PartitionSpec("b"))
            gains, n_samples, cpu_freq, ages, model_bits = (
                jax.device_put(jnp.asarray(x, jnp.float32), sh)
                for x in (gains, n_samples, cpu_freq, ages, model_bits))
            if priority is not None:
                priority = jax.device_put(
                    jnp.asarray(priority, jnp.float32), sh)
        pairing = self.pairing if pairing is None else pairing
        selection = self.selection if selection is None else selection
        if selection not in SELECTIONS:
            raise ValueError(f"unknown selection mode {selection!r} "
                             f"(expected one of {SELECTIONS})")
        no_budget = (isinstance(t_budget, (int, float))
                     and float(t_budget) <= 0.0)
        n_cells = self.flcfg.n_cells if n_cells is None else n_cells
        if cell is not None and n_cells > 1:
            cap = cell_capacity(n, n_cells, self.prm.slots)
            n_cand0 = min(self.prm.slots, cap)
            adm = resolve_admission(
                self.admission if admission is None else admission,
                cap, n_cand0)
            if priority is None:
                priority = self.age_priority(ages, n_samples, gains)
            t_cmp = self.compute_times(n_samples,
                                       jnp.asarray(cpu_freq, jnp.float32))
            tb = (jnp.zeros((b,), jnp.float32) if no_budget
                  else jnp.broadcast_to(
                      jnp.asarray(t_budget, jnp.float32), (b,)))
            out = _per_shard(functools.partial(
                _multicell_schedule_core, prm=self.prm, oma=oma,
                pairing=pairing, selection=selection, admission=adm,
                n_cells=n_cells, cap=cap, budget=not no_budget,
                impl=self.impl), mesh,
                jnp.asarray(priority, jnp.float32), gains, t_cmp,
                n_samples, model_bits, tb, jnp.asarray(cell, jnp.int32))
            if self.use_pallas and not no_budget:
                # fast cells already scored in-kernel; the budget cells'
                # eviction loop is XLA and gets the post-hoc rescore
                out = self._rescore(out, gains, model_bits, oma, mesh)
            return out
        admission = resolve_admission(
            self.admission if admission is None else admission, n, n_cand0)
        if no_budget and priority is None:
            # fully fused: age priority + T_cmp + fast path in one dispatch
            out = _per_shard(functools.partial(
                _fast_from_env_core, prm=self.prm,
                gamma=self.flcfg.age_exponent, oma=oma, n_pairs=n_pairs,
                n_cand0=n_cand0, pairing=pairing, selection=selection,
                admission=admission, impl=self.impl), mesh,
                gains, n_samples, jnp.asarray(cpu_freq, jnp.float32), ages,
                model_bits)
        elif no_budget:
            priority = jnp.asarray(priority, jnp.float32)
            t_cmp = self.compute_times(n_samples,
                                       jnp.asarray(cpu_freq, jnp.float32))
            out = _per_shard(functools.partial(
                _fast_schedule_batch_core, prm=self.prm, oma=oma,
                n_pairs=n_pairs, n_cand0=n_cand0, pairing=pairing,
                selection=selection, admission=admission, impl=self.impl),
                mesh, priority, gains, t_cmp, n_samples, model_bits)
        else:
            if priority is None:
                priority = self.age_priority(ages, n_samples, gains)
            priority = jnp.asarray(priority, jnp.float32)
            t_cmp = self.compute_times(n_samples,
                                       jnp.asarray(cpu_freq, jnp.float32))
            t_budget = jnp.broadcast_to(jnp.asarray(t_budget, jnp.float32),
                                        (b,))
            out = _per_shard(functools.partial(
                _schedule_batch_core, prm=self.prm, oma=oma,
                n_pairs=n_pairs, n_cand0=n_cand0, pairing=pairing,
                selection=selection), mesh,
                priority, gains, t_cmp, n_samples, model_bits, t_budget)
            if self.use_pallas:
                out = self._rescore(out, gains, model_bits, oma, mesh)
        return out

    def _rescore(self, out: EngineSchedule, gains, model_bits,
                 oma: bool, mesh=None) -> EngineSchedule:
        return _per_shard(functools.partial(
            _rescore_pallas, oma=oma, prm=self.prm, impl=self.pallas_impl),
            mesh, out, gains, model_bits)

    def schedule(self, env: RoundEnv, *, t_budget: Optional[float] = None,
                 oma: bool = False, priority=None,
                 policy: str = "age_noma",
                 pairing: Optional[str] = None,
                 selection: Optional[str] = None,
                 cell=None) -> Schedule:
        """Single-env convenience wrapper returning the numpy ``Schedule``
        (drop-in for ``schedule_age_noma``; used by ``FLServer``)."""
        if t_budget is None:
            t_budget = self.flcfg.t_budget_s
        batchify = lambda a: jnp.asarray(a)[None]
        out = self.schedule_batch(
            batchify(env.gains), batchify(env.n_samples),
            batchify(env.cpu_freq), batchify(env.ages), env.model_bits,
            t_budget=t_budget, oma=oma, pairing=pairing,
            selection=selection,
            priority=None if priority is None else batchify(priority),
            cell=None if cell is None else batchify(cell))
        return engine_schedule_to_numpy(out, 0, info={
            "policy": policy, "engine": "jax",
            "evicted": np.flatnonzero(
                np.asarray(out.evicted[0])).tolist()})

    # -- Monte-Carlo rollout ----------------------------------------------

    def montecarlo_rounds(self, gains_seq, n_samples, cpu_freq, model_bits,
                          *, policy: str = "age_noma", t_budget: float = 0.0,
                          seed: int = 0, shard: bool = False,
                          pairing: Optional[str] = None,
                          selection: Optional[str] = None,
                          admission: Optional[str] = None,
                          cell_seq=None):
        """Roll the AoU state machine over R rounds for S seeds, one batched
        step per round: gains_seq (R, S, N); n_samples/cpu_freq either
        (S, N) static or (R, S, N) per-round (the scenario ``presampled=``
        escape hatch — see ``montecarlo_scenario`` for the fused path).
        ``cell_seq`` ((R, S, N) int) activates the cell-partitioned
        planner when ``FLConfig.n_cells > 1``.

        Returns dict of stacked per-round metrics (t_round (R, S),
        n_selected (R, S), max_age (R, S)), the diag leaves of the
        telemetry contract (t_comp_bottleneck / t_up_bottleneck (R, S),
        n_evicted (R, S) int32, aou_hist (R, S, 7) int32 — DESIGN.md
        section 11), plus participation (S, N) and, under multi-cell,
        per-round ``handovers`` (R, S).
        ``shard=True`` splits the independent seeds over all devices (a
        seed count that does not divide over them raises).
        """
        gains_seq = jnp.asarray(gains_seq, jnp.float32)
        r, s, n = gains_seq.shape
        n_samples = jnp.asarray(n_samples, jnp.float32)
        cpu_freq = jnp.asarray(cpu_freq, jnp.float32)
        mesh = _shard_mesh(s, "s", "n_seeds") if shard else None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            seq = NamedSharding(mesh, PartitionSpec(None, "s"))
            per_seed = NamedSharding(mesh, PartitionSpec("s"))
            gains_seq = jax.device_put(gains_seq, seq)
            n_samples, cpu_freq = (
                jax.device_put(x, per_seed if x.ndim == 2 else seq)
                for x in (n_samples, cpu_freq))

        if cell_seq is not None:
            cell_seq = jnp.asarray(cell_seq, jnp.int32)

        def env_fn(i):
            return (gains_seq[i],
                    n_samples if n_samples.ndim == 2 else n_samples[i],
                    cpu_freq if cpu_freq.ndim == 2 else cpu_freq[i],
                    None if cell_seq is None else cell_seq[i])

        return self._mc_loop(env_fn, r, model_bits, policy=policy,
                             t_budget=t_budget, seed=seed, pairing=pairing,
                             selection=selection, admission=admission,
                             mesh=mesh)

    def montecarlo_scenario(self, scenario, *, rounds: int, n_seeds: int,
                            n_clients: int, model_bits,
                            policy: str = "age_noma", t_budget: float = 0.0,
                            seed: int = 0, key=None, shard: bool = False,
                            pairing: Optional[str] = None,
                            selection: Optional[str] = None,
                            admission: Optional[str] = None):
        """Fully fused Monte-Carlo: the scenario's ``step(state, key) ->
        (state, env)`` transition advances the wireless environment on
        device between scheduled rounds — no host-side R x S x N gains
        materialization ever exists (DESIGN.md section 6).

        ``scenario`` is duck-typed (``repro.sim.Scenario``): the engine
        only calls ``init_and_keys(key, rounds, (S, N))`` and
        ``step(state, key)``. ``key`` defaults to ``PRNGKey(seed)`` —
        ``fl.rounds.run_montecarlo`` passes the same key to
        ``Scenario.rollout`` so the ``presampled=`` path is bit-identical.
        """
        if key is None:
            key = jax.random.PRNGKey(seed)
        state, env_keys = scenario.init_and_keys(
            key, rounds, (n_seeds, n_clients))
        mesh = _shard_mesh(n_seeds, "s", "n_seeds") if shard else None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            state = jax.device_put(
                state, NamedSharding(mesh, PartitionSpec("s")))
        box = [state]

        def env_fn(i):
            box[0], env = scenario.step(box[0], env_keys[i])
            return (env.gains, env.n_samples, env.cpu_freq,
                    getattr(env, "cell", None))

        return self._mc_loop(env_fn, rounds, model_bits, policy=policy,
                             t_budget=t_budget, seed=seed, pairing=pairing,
                             selection=selection, admission=admission,
                             mesh=mesh)

    def _mc_loop(self, env_fn, rounds: int, model_bits, *, policy: str,
                 t_budget: float, seed: int,
                 pairing: Optional[str] = None,
                 selection: Optional[str] = None,
                 admission: Optional[str] = None, mesh=None):
        """R-round rollout: a Python loop of jitted per-round steps rather
        than ``lax.scan`` — on CPU the XLA while-loop runs the identical
        body ~1.7x slower than back-to-back jit dispatches. ``env_fn(i)``
        yields round i's (gains, n_samples, cpu_freq, cell-or-None),
        either sliced from pre-sampled arrays or stepped out of a
        scenario state. With ``FLConfig.n_cells > 1`` and a non-None
        cell, each step runs the cell-partitioned planner and the output
        gains per-round handover counts. ``mesh`` (from ``shard=True``)
        keeps the age state on the seed axis and plans each device's seeds
        on that device."""
        pairing = self.pairing if pairing is None else pairing
        selection = self.selection if selection is None else selection
        if selection not in SELECTIONS:
            raise ValueError(f"unknown selection mode {selection!r} "
                             f"(expected one of {SELECTIONS})")
        admission = self.admission if admission is None else admission
        n_cells = self.flcfg.n_cells
        keys = jax.random.split(jax.random.PRNGKey(seed), rounds)
        mb = jnp.asarray(model_bits, jnp.float32)
        ages = part = None
        multicell = False
        cap = 0
        prev_cell = None
        t_rounds, n_sels, max_ages, handovers = [], [], [], []
        t_comp_bs, t_up_bs, n_evs, aou_hists = [], [], [], []
        mc_span = trace.span("engine.mc_loop", rounds=rounds, policy=policy,
                             seed=seed)
        with mc_span as sp:
            for i in range(rounds):
                gains, n_samples, cpu_freq, cellv = env_fn(i)
                if ages is None:
                    s, n = gains.shape
                    multicell = n_cells > 1 and cellv is not None
                    if multicell:
                        cap = cell_capacity(n, n_cells, self.prm.slots)
                        n_cand0 = min(self.prm.slots, cap)
                        admission = resolve_admission(admission, cap,
                                                      n_cand0)
                    else:
                        n_cand0 = min(self.prm.slots, n)
                        admission = resolve_admission(admission, n, n_cand0)
                    n_pairs = max((n_cand0 + 1) // 2, 1)
                    ages = jnp.ones((s, n), jnp.float32)
                    part = jnp.zeros((s, n), jnp.float32)
                    if mesh is not None:
                        from jax.sharding import NamedSharding, PartitionSpec
                        ages, part = jax.device_put(
                            (ages, part),
                            NamedSharding(mesh, PartitionSpec("s")))
                    sp.note(s=s, n=n, cold=trace.cold(
                        ("mc", s, n, policy, pairing, selection, admission,
                         float(t_budget), multicell)))
                (ages, part, t_round, n_sel, max_age, t_comp_b, t_up_b,
                 n_ev, aou_h) = _montecarlo_step(
                    ages, part, gains, keys[i], n_samples, cpu_freq, mb,
                    jnp.asarray(i, jnp.int32),
                    cellv if multicell else None,
                    prm=self.prm, gamma=self.flcfg.age_exponent,
                    policy=policy,
                    t_budget=float(t_budget), n_pairs=n_pairs,
                    n_cand0=n_cand0,
                    pairing=pairing, selection=selection,
                    admission=admission, impl=self.impl,
                    n_cells=n_cells if multicell else 1, cap=cap, mesh=mesh)
                t_rounds.append(t_round)
                n_sels.append(n_sel)
                max_ages.append(max_age)
                t_comp_bs.append(t_comp_b)
                t_up_bs.append(t_up_b)
                n_evs.append(n_ev)
                aou_hists.append(aou_h)
                if multicell:
                    handovers.append(
                        jnp.zeros(gains.shape[0], jnp.int32)
                        if prev_cell is None
                        else jnp.sum((cellv != prev_cell).astype(jnp.int32),
                                     axis=1))
                    prev_cell = cellv
            out = {"t_round": jnp.stack(t_rounds),
                   "n_selected": jnp.stack(n_sels),
                   "max_age": jnp.stack(max_ages), "participation": part,
                   "final_ages": ages,
                   "t_comp_bottleneck": jnp.stack(t_comp_bs),
                   "t_up_bottleneck": jnp.stack(t_up_bs),
                   "n_evicted": jnp.stack(n_evs),
                   "aou_hist": jnp.stack(aou_hists)}
            if multicell:
                out["handovers"] = jnp.stack(handovers)
            sp.fence(out["t_round"])
        return out


@functools.partial(jax.jit, static_argnames=("prm", "gamma", "policy",
                                             "t_budget", "n_pairs",
                                             "n_cand0", "pairing",
                                             "selection", "admission",
                                             "impl", "n_cells",
                                             "cap", "mesh"))
def _montecarlo_step(ages, part, gains, key, n_samples, cpu_freq,
                     model_bits, round_idx, cell=None, *,
                     prm: EngineParams,
                     gamma: float, policy: str, t_budget: float,
                     n_pairs: int, n_cand0: int,
                     pairing: str = "strong_weak",
                     selection: str = "greedy_set",
                     admission: str = "full_sort",
                     impl: str = "xla",
                     n_cells: int = 1, cap: int = 0, mesh=None):
    """One Monte-Carlo round over all seeds; every policy in
    ``fl.rounds.POLICIES`` resolves to a priority vector here
    (``age_noma_budget`` is age priority + the caller's positive
    ``t_budget``). ``round_idx`` is traced so the round-robin window can
    advance without recompiling. A non-None ``cell`` with ``n_cells > 1``
    routes through the cell-partitioned planner (``n_cand0``/``n_pairs``
    are then the per-cell values for capacity ``cap``). ``impl`` routes
    the fast paths' scoring in-kernel; the budget path rescores post-hoc
    (see ``_rescore_pallas``). With a ``mesh`` the priorities are drawn
    over the whole seed axis and each device plans its own seeds. The
    stages are named scopes (``mc.priority``, ``mc.admit``,
    ``mc.finish``, ``mc.ages``), so each op in a device trace carries the
    stage it belongs to."""
    s, n = gains.shape
    oma = policy == "oma_age"
    with jax.named_scope("mc.priority"):
        t_cmp = _compute_times(prm, n_samples, cpu_freq)
        mb = jnp.broadcast_to(model_bits, (s,))
        if policy in ("age_noma", "age_noma_budget", "oma_age"):
            prio = _age_priority(ages, n_samples, gains, gamma)
        elif policy == "channel":
            prio = gains
        elif policy == "random":
            prio = jax.random.uniform(key, gains.shape)
        elif policy == "round_robin":
            prio = jnp.broadcast_to(
                round_robin_priority(round_idx, n, n_cand0), gains.shape)
        else:
            raise ValueError(f"unknown montecarlo policy {policy!r}")

    def plan(prio, gains, t_cmp, n_samples, mb, cell):
        rows = gains.shape[0]
        if cell is not None and n_cells > 1:
            tb = jnp.full((rows,), t_budget, jnp.float32)
            sched = _multicell_schedule(
                prio, gains, t_cmp, n_samples, mb, tb, cell, prm=prm,
                oma=oma, pairing=pairing, selection=selection,
                admission=admission, n_cells=n_cells, cap=cap,
                budget=t_budget > 0.0, impl=impl)
            if t_budget > 0.0 and impl != "xla":
                sched = _rescore_pallas(sched, gains, mb, oma, prm, impl)
            return sched
        if t_budget <= 0.0:
            def step(p, g, tc, ns, mbx):
                return _fast_schedule_batch(p, g, tc, ns, mbx, prm, oma,
                                            n_pairs, n_cand0, pairing,
                                            selection, admission, impl)

            sub = (_seg_subchunk(rows, n) if admission == "segmented"
                   else 0)
            if sub:
                return _scan_subchunks(
                    step, (prio, gains, t_cmp, n_samples, mb), rows, sub)
            return step(prio, gains, t_cmp, n_samples, mb)
        tb = jnp.full((rows,), t_budget, jnp.float32)
        one = functools.partial(_schedule_one, prm=prm, oma=oma,
                                n_pairs=n_pairs, n_cand0=n_cand0,
                                pairing=pairing, selection=selection)
        sched = jax.vmap(one)(prio, gains, t_cmp, n_samples, mb, tb)
        if impl != "xla":
            sched = _rescore_pallas(sched, gains, mb, oma, prm, impl)
        return sched

    sched = _per_shard(plan, mesh, prio, gains, t_cmp, n_samples, mb,
                       cell if n_cells > 1 else None)
    with jax.named_scope("mc.ages"):
        sel = sched.selected
        ages2 = jnp.where(sel, 1.0, ages + 1.0)
        diag = schedule_diag(sched, ages2)
        return (ages2, part + sel, sched.t_round, jnp.sum(sel, axis=1),
                jnp.max(ages2, axis=1), diag["t_comp_bottleneck"],
                diag["t_up_bottleneck"], diag["n_evicted"],
                diag["aou_hist"])


def engine_schedule_to_numpy(out: EngineSchedule, b: int,
                             info: Optional[dict] = None) -> Schedule:
    """Extract batch element ``b`` as the host-side ``Schedule`` dataclass
    (pairs as [(strong, weak)] with weak=-1 solo, pad rows removed)."""
    strong = np.asarray(out.pair_strong[b])
    weak = np.asarray(out.pair_weak[b])
    pairs = [(int(i), int(j)) for i, j in zip(strong, weak) if i >= 0]
    # host boundary: widening fp32 device outputs to the fp64 Schedule
    # contract the numpy reference exposes — not engine-side arithmetic
    return Schedule(
        selected=np.asarray(out.selected[b]),
        pairs=pairs,
        rates=np.asarray(out.rates[b], np.float64),      # reprolint: disable=precision-contract
        powers=np.asarray(out.powers[b], np.float64),    # reprolint: disable=precision-contract
        t_cmp=np.asarray(out.t_cmp[b], np.float64),      # reprolint: disable=precision-contract
        t_com=np.asarray(out.t_com[b], np.float64),      # reprolint: disable=precision-contract
        t_round=float(out.t_round[b]),
        agg_weights=np.asarray(out.agg_weights[b], np.float64),  # reprolint: disable=precision-contract
        info=info or {"engine": "jax"},
    )
