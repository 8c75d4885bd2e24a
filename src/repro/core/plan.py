"""Round planner: ONE staged select->pair->allocate pipeline for both
engines (numpy fp64 reference; the batched jit/vmap twins live in
``core/engine.py`` and mirror these stages function-for-function).

The paper's joint round decomposes into explicit stages (DESIGN.md
section 8):

  1. score      policy priority vector (``age_score`` is the paper's
                A_n^gamma * w_n; channel / round-robin / random priorities
                resolve in the drivers);
  2. admit      ``greedy_set``: top-slots by the (priority desc, gain desc,
                index asc) lexicographic order (``admission_order`` — the
                single tiebreak definition both engines transcribe);
                ``joint``: pairing-aware refinement on top of the greedy
                seed (``joint_admission``) — admit the set whose best
                matching minimizes round time, exhaustive for
                n <= JOINT_ENUM_MAX_N, swap/prune local search above, with
                a never-worse-than-greedy guard on the realized round time;
  3. match      subchannel pairing of the admitted set under
                ``FLConfig.pairing`` (``match_candidates`` ->
                core/pairing.py; odd counts park the weakest candidate on
                a solo subchannel);
  4. allocate   closed-form max-min power per pair -> SIC rates
                (``allocate_rates`` -> core/noma.py);
  5. time       T_cmp + T_com per client, T_round = max over selected
                (``finalize`` -> core/roundtime.py);

plus the round-time budget eviction/backfill loop that drives stages 3-5
(``plan_round``). ``scheduler.schedule_*``, ``FLServer.select()`` and the
engine cores are thin drivers over this module — the triplicated
priority/tiebreak/eviction logic of PRs 1-4 lives only here.

Shared selection contract (transcribed by ``engine._joint_refine_mask``):

  * ``enumerate_subsets(n, c)`` fixes the subset enumeration order — the
    deterministic argmin-first tiebreak both engines share (the
    ``enumerate_matchings`` pattern from PR 4);
  * the swap search evaluates sets on the strong_weak completion of the
    gain-sorted half-split (``sw_completion``) and swaps the bottleneck
    client for the non-member with the best solo completion proxy,
    ``JOINT_SWAP_ITERS`` times, first non-improving swap stops;
  * the guard compares the REALIZED round time of the refined set against
    the greedy set under the active pairing policy and keeps greedy unless
    the refinement is strictly faster — so ``selection="joint"`` is never
    slower than ``greedy_set`` per round, for every pairing policy, by
    construction.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Optional

import numpy as np

from repro.configs.base import FLConfig, NOMAConfig, check_choice
from repro.core import aoi, noma, pairing, roundtime
from repro.obs import trace
from repro.obs.metrics import aou_histogram

# FLConfig.admission = "auto" picks the engine's admission implementation
# by population size: below this many clients the two full_sort bitonic
# half-sorts are cheap enough that the threshold-search constant factor —
# 32 count passes — is not worth paying; from here up the segmented
# path's O(N) passes win and keep winning (BENCH_admission_scaling on
# CPU: ~1.3x at N=256 growing to ~7x at N=16000; the admitted set is
# bit-for-bit identical either way — DESIGN.md section 9)
ADMISSION_AUTO_N = 256


def resolve_admission(mode: str, n: int, c: int) -> str:
    """Resolve an ``FLConfig.admission`` mode to the concrete stage-2
    implementation for an (N clients, c slots) instance. Explicit modes
    pass through (never silently overridden); unknown modes raise."""
    if check_choice("admission", mode) != "auto":
        return mode
    return "segmented" if n >= ADMISSION_AUTO_N else "full_sort"

# n <= this: joint admission enumerates ALL C(n, c) candidate sets x all
# matchings (the exhaustive joint optimum the C4-style reference checks);
# above it the swap/prune local search runs
JOINT_ENUM_MAX_N = 8

# swap/prune local search length: each iteration swaps the bottleneck
# client for the best-proxy non-member and keeps the swap only on a strict
# strong_weak-completion improvement (both engines unroll exactly this many)
JOINT_SWAP_ITERS = 4


@dataclasses.dataclass
class RoundEnv:
    """Per-round wireless + client state visible to the scheduler."""
    gains: np.ndarray        # (N,) channel power gains this round
    n_samples: np.ndarray    # (N,) local dataset sizes
    cpu_freq: np.ndarray     # (N,) Hz
    ages: np.ndarray         # (N,) AoU
    model_bits: float        # uplink payload


@dataclasses.dataclass
class Schedule:
    selected: np.ndarray                 # (N,) bool
    pairs: list                          # [(strong, weak), ...]; weak=-1 solo
    rates: np.ndarray                    # (N,) bits/s (0 unselected)
    powers: np.ndarray                   # (N,) W
    t_cmp: np.ndarray                    # (N,) s
    t_com: np.ndarray                    # (N,) s
    t_round: float
    agg_weights: np.ndarray              # (N,) aggregation weights
    info: dict


# ---------------------------------------------------------------------------
# stage 1: score
# ---------------------------------------------------------------------------


def age_score(env: RoundEnv, flcfg: FLConfig) -> np.ndarray:
    """The paper's selection key A_n^gamma * w_n (engine twin:
    ``engine._age_priority``)."""
    w = env.n_samples / env.n_samples.sum()
    return aoi.age_priority(env.ages, w, flcfg.age_exponent)


# ---------------------------------------------------------------------------
# diagnostics (engine twin: ``engine.schedule_diag``)
# ---------------------------------------------------------------------------


def schedule_diag(sched: Schedule, ages: Optional[np.ndarray] = None, *,
                  cell: Optional[np.ndarray] = None,
                  n_cells: int = 1) -> dict:
    """Fixed-shape per-round diagnostics of a ``Schedule`` — the numpy
    reference of the telemetry contract (DESIGN.md section 11; jax twin:
    ``engine.schedule_diag``, parity-tested leaf-for-leaf).

    The bottleneck decomposition is exact by construction: the round time
    is the max over selected clients of t_cmp + t_com, so the argmax
    client's ``t_comp_bottleneck + t_up_bottleneck == t_round`` to fp
    precision (single- and multi-cell alike — cells transmit in parallel
    and the global round time is the slowest cell's bottleneck client).
    ``n_evicted`` equals the budget-loop iteration count (each iteration
    evicts exactly one client). ``aou_hist`` buckets the FULL population's
    ages on ``metrics.AOU_BUCKET_EDGES`` when ``ages`` is given;
    ``sel_per_cell`` counts selected clients per cell when a cell map is
    given.
    """
    sel = np.asarray(sched.selected, dtype=bool)
    tot = np.where(sel, sched.t_cmp + sched.t_com, 0.0)
    b = int(np.argmax(tot))
    any_sel = bool(sel.any())
    info = sched.info or {}
    if "evicted" in info:
        n_evicted = len(info["evicted"])
        n_swaps = info.get("joint_swaps_accepted", 0)
    else:
        cells = info.get("cells", ())
        n_evicted = sum(len(c.get("evicted", ())) for c in cells)
        n_swaps = sum(c.get("joint_swaps_accepted", 0) for c in cells)
    diag = {
        "t_round": float(sched.t_round),
        "t_comp_bottleneck": float(sched.t_cmp[b]) if any_sel else 0.0,
        "t_up_bottleneck": float(sched.t_com[b]) if any_sel else 0.0,
        "n_selected": int(sel.sum()),
        "n_evicted": int(n_evicted),
        "joint_swaps_accepted": int(n_swaps),
    }
    if ages is not None:
        diag["aou_hist"] = aou_histogram(ages)
    if cell is not None and n_cells > 1:
        diag["sel_per_cell"] = np.bincount(
            np.asarray(cell, dtype=int)[sel], minlength=n_cells
        ).astype(np.int64)
    return diag


# ---------------------------------------------------------------------------
# stage 2: admit
# ---------------------------------------------------------------------------


def admission_order(priority: np.ndarray, gains: np.ndarray) -> np.ndarray:
    """(priority desc, gain desc, index asc) lexicographic client ranking —
    THE selection tiebreak (PR 4's fix; the old ``prio + 1e-12 * gains``
    epsilon was numerically vacuous). Engine twins: the fast path's
    threshold passes and the budget core's ``jnp.lexsort``."""
    n = len(gains)
    return np.lexsort((np.arange(n), -np.asarray(gains),
                       -np.asarray(priority)))


@functools.lru_cache(maxsize=None)
def enumerate_subsets(n: int, c: int) -> np.ndarray:
    """All size-``c`` subsets of ``range(n)`` as a (C(n,c), c) int array in
    ``itertools.combinations`` order — the shared deterministic enumeration
    (and argmin-first tiebreak) of the joint admission stage, used verbatim
    by the numpy reference, the engine's static gather tables, and the
    exhaustive joint reference (so they can never disagree on coverage or
    order)."""
    return np.array(list(itertools.combinations(range(n), c)),
                    dtype=np.int64).reshape(-1, c)


def _solo_completion(client: int, env: RoundEnv, t_cmp: np.ndarray,
                     ncfg: NOMAConfig) -> float:
    r = noma.solo_rate(ncfg.max_power_w, env.gains[client], ncfg)
    return float(t_cmp[client] + env.model_bits / max(float(r), 1e-9))


def set_best_time(subset, env: RoundEnv, t_cmp: np.ndarray,
                  ncfg: NOMAConfig, *, oma: bool = False) -> float:
    """Round-time of ``subset`` under its OPTIMAL pairing: exact bottleneck
    over all perfect matchings of the gain-sorted members (solo convention:
    the weakest member — last in ``noma.pairing_order`` — when odd). The
    joint enumeration objective; tiny sets only (m <= ENUM_MAX_PAIRS)."""
    order = noma.pairing_order(env.gains, np.asarray(subset, dtype=int))
    t = 0.0
    if len(order) % 2 == 1:
        t = _solo_completion(int(order[-1]), env, t_cmp, ncfg)
        order = order[:-1]
    m = len(order) // 2
    if m:
        table = pairing.completion_table(
            env.gains[order], env.gains[order], t_cmp[order], t_cmp[order],
            env.model_bits, ncfg, oma=oma)
        mt = pairing.enumerate_matchings(m)
        t = max(t, float(table[mt[:, :, 0], mt[:, :, 1]].max(axis=1).min()))
    return t


def sw_completion(cand, env: RoundEnv, t_cmp: np.ndarray, ncfg: NOMAConfig,
                  *, oma: bool = False):
    """Per-member completion times of ``cand`` under strong_weak pairing,
    aligned to the (gain desc, index asc) sorted rank — the swap search's
    cheap evaluation surface (engine twin: ``engine._sw_completion``).
    Returns (t_round, completions (c,), sorted client order (c,))."""
    order = noma.pairing_order(env.gains, np.asarray(cand, dtype=int))
    c = len(order)
    cp = c - (c % 2)
    m = cp // 2
    comp = np.zeros(c)
    if m:
        strong = order[:m]
        weak = order[cp - 1:m - 1:-1]          # rank cp-1-p pairs rank p
        g_i, g_j = env.gains[strong], env.gains[weak]
        if oma:
            pm = np.full(m, ncfg.max_power_w)
            r_i, r_j = noma.oma_pair_rates(pm, pm, g_i, g_j, ncfg)
        else:
            p_i, p_j = noma.pair_power_allocation(g_i, g_j, ncfg)
            r_i, r_j = noma.pair_rates(p_i, p_j, g_i, g_j, ncfg)
        comp[:m] = t_cmp[strong] + env.model_bits / np.maximum(r_i, 1e-9)
        comp[m:cp] = (t_cmp[weak] + env.model_bits
                      / np.maximum(r_j, 1e-9))[::-1]
    if c % 2:
        comp[c - 1] = _solo_completion(int(order[-1]), env, t_cmp, ncfg)
    return float(comp.max()) if c else 0.0, comp, order


def joint_admission(cand, env: RoundEnv, ncfg: NOMAConfig, flcfg: FLConfig,
                    *, oma: bool = False,
                    pairing_policy: Optional[str] = None,
                    diag: Optional[dict] = None) -> list:
    """Pairing-aware refinement of the greedy admitted set ``cand``:

    * ``n <= JOINT_ENUM_MAX_N``: enumerate every C(n, c) candidate set and
      take the one whose optimal matching minimizes round time
      (argmin-first over ``enumerate_subsets`` order);
    * otherwise: ``JOINT_SWAP_ITERS`` rounds of swap/prune local search —
      evict the bottleneck client of the strong_weak completion, admit the
      non-member with the best solo-completion proxy, keep the swap only
      on a strict improvement, stop at the first rejection;
    * never-worse guard: the refined set replaces ``cand`` only when its
      REALIZED round time under the active pairing policy strictly beats
      the greedy set's.

    ``diag`` (optional dict) collects refinement telemetry in place:
    ``joint_swaps_accepted`` (accepted local-search swaps) and
    ``joint_kept`` (did the guard keep the refined set).
    """
    if diag is None:
        diag = {}
    diag.setdefault("joint_swaps_accepted", 0)
    diag.setdefault("joint_kept", False)
    flcfg = (flcfg if pairing_policy is None
             else dataclasses.replace(flcfg, pairing=pairing_policy))
    n = len(env.gains)
    c = len(cand)
    if c < 1 or c >= n:
        return list(cand)
    t_cmp = roundtime.compute_times(env.n_samples,
                                    flcfg.cpu_cycles_per_sample,
                                    env.cpu_freq, flcfg.local_epochs)
    if n <= JOINT_ENUM_MAX_N:
        subsets = enumerate_subsets(n, c)
        times = [set_best_time(s, env, t_cmp, ncfg, oma=oma)
                 for s in subsets]
        refined = [int(x) for x in subsets[int(np.argmin(times))]]
    else:
        refined = _swap_search(cand, env, t_cmp, ncfg, oma=oma, diag=diag)
    if set(refined) == set(cand):
        return list(cand)
    t_greedy = finalize(cand, env, ncfg, flcfg, oma, {}).t_round
    t_joint = finalize(refined, env, ncfg, flcfg, oma, {}).t_round
    diag["joint_kept"] = bool(t_joint < t_greedy)
    return refined if t_joint < t_greedy else list(cand)


def _swap_search(cand, env: RoundEnv, t_cmp: np.ndarray, ncfg: NOMAConfig,
                 *, oma: bool = False, diag: Optional[dict] = None) -> list:
    """Swap/prune local search (see ``joint_admission``). The solo
    completion proxy prunes the swap-in choice to one candidate per
    iteration; acceptance is exact on the strong_weak completion."""
    n = len(env.gains)
    proxy = t_cmp + env.model_bits / np.maximum(
        noma.solo_rate(ncfg.max_power_w, env.gains, ncfg), 1e-9)
    cur = [int(x) for x in cand]
    cur_t, comp, order = sw_completion(cur, env, t_cmp, ncfg, oma=oma)
    for _ in range(JOINT_SWAP_ITERS):
        bottleneck = int(order[int(np.argmax(comp))])
        member = np.zeros(n, bool)
        member[cur] = True
        incoming = int(np.argmin(np.where(member, np.inf, proxy)))
        new = [x for x in cur if x != bottleneck] + [incoming]
        new_t, new_comp, new_order = sw_completion(new, env, t_cmp, ncfg,
                                                   oma=oma)
        if not new_t < cur_t:
            break
        if diag is not None:
            diag["joint_swaps_accepted"] += 1
        cur, cur_t, comp, order = new, new_t, new_comp, new_order
    return cur


# ---------------------------------------------------------------------------
# stages 3 + 4: match + allocate
# ---------------------------------------------------------------------------


def match_candidates(cand, env: RoundEnv, ncfg: NOMAConfig, *,
                     pairing_policy: str = "strong_weak",
                     t_cmp: Optional[np.ndarray] = None, oma: bool = False):
    """Split an odd set's weakest candidate onto a solo subchannel, pair
    the rest under ``pairing_policy`` (core/pairing.py). Returns
    (pairs, solo-or-None)."""
    cand = np.asarray(cand, dtype=int)
    solo = None
    if len(cand) % 2 == 1:
        solo = int(cand[np.argmin(env.gains[cand])])
        cand = cand[cand != solo]
    pairs = pairing.pair_candidates(env.gains, cand, pairing_policy,
                                    t_cmp=t_cmp,
                                    model_bits=env.model_bits, ncfg=ncfg,
                                    oma=oma)
    return pairs, solo


def allocate_rates(pairs, solo, env: RoundEnv, ncfg: NOMAConfig, *,
                   oma: bool = False):
    """Closed-form max-min power per pair -> SIC rates (full power for the
    solo subchannel). Returns (pairs incl. the (solo, -1) row, rates (N,),
    powers (N,))."""
    n = len(env.gains)
    rates = np.zeros(n)
    powers = np.zeros(n)
    if pairs:
        gi = env.gains[[p[0] for p in pairs]]
        gj = env.gains[[p[1] for p in pairs]]
        if oma:
            p_i = np.full(len(pairs), ncfg.max_power_w)
            p_j = np.full(len(pairs), ncfg.max_power_w)
            r_i, r_j = noma.oma_pair_rates(p_i, p_j, gi, gj, ncfg)
        else:
            p_i, p_j = noma.pair_power_allocation(gi, gj, ncfg)
            r_i, r_j = noma.pair_rates(p_i, p_j, gi, gj, ncfg)
        for m, (i, j) in enumerate(pairs):
            rates[i], rates[j] = r_i[m], r_j[m]
            powers[i], powers[j] = p_i[m], p_j[m]
    out_pairs = [(i, j) for (i, j) in pairs]
    if solo is not None:
        rates[solo] = noma.solo_rate(ncfg.max_power_w, env.gains[solo], ncfg)
        powers[solo] = ncfg.max_power_w
        out_pairs.append((solo, -1))
    return out_pairs, rates, powers


# ---------------------------------------------------------------------------
# stage 5: time (+ Schedule assembly)
# ---------------------------------------------------------------------------


def finalize(cand, env: RoundEnv, ncfg: NOMAConfig, flcfg: FLConfig,
             oma: bool, info: dict) -> Schedule:
    """Stages 3-5 for a fixed admitted set ``cand`` -> Schedule."""
    n = len(env.gains)
    t_cmp = roundtime.compute_times(env.n_samples,
                                    flcfg.cpu_cycles_per_sample,
                                    env.cpu_freq, flcfg.local_epochs)
    pairs, solo = match_candidates(cand, env, ncfg,
                                   pairing_policy=flcfg.pairing,
                                   t_cmp=t_cmp, oma=oma)
    pairs, rates, powers = allocate_rates(pairs, solo, env, ncfg, oma=oma)
    selected = np.zeros(n, dtype=bool)
    selected[list(cand)] = True
    t_com = roundtime.comm_times(env.model_bits, rates)
    t_rd = roundtime.round_time(t_cmp, t_com, selected)
    w = env.n_samples.astype(np.float64) * selected
    w = w / max(w.sum(), 1e-12)
    return Schedule(selected, pairs, rates, powers, t_cmp, t_com, t_rd, w,
                    info)


# ---------------------------------------------------------------------------
# drivers: full pipeline + budget loop
# ---------------------------------------------------------------------------


def plan_round(env: RoundEnv, ncfg: NOMAConfig, flcfg: FLConfig, *,
               priority: np.ndarray, oma: bool = False,
               info: Optional[dict] = None,
               t_budget: Optional[float] = None,
               selection: Optional[str] = None) -> Schedule:
    """The full staged pipeline for a priority-based policy: admit (greedy
    or joint) -> match -> allocate -> time, then the budget
    eviction/backfill loop (engine twin: ``engine._schedule_one``)."""
    selection = check_choice(
        "selection", flcfg.selection if selection is None else selection)
    t_budget = flcfg.t_budget_s if t_budget is None else t_budget
    n = len(env.gains)
    slots = ncfg.n_subchannels * ncfg.users_per_subchannel
    with trace.span("plan.admit", n=n, slots=slots):
        order = admission_order(priority, env.gains)
        cand = [int(x) for x in order[:min(slots, n)]]
    base = dict(info or {})
    if selection == "joint":
        with trace.span("plan.joint", n=n) as sp:
            cand = joint_admission(cand, env, ncfg, flcfg, oma=oma,
                                   diag=base)
            sp.note(swaps=base["joint_swaps_accepted"],
                    kept=base["joint_kept"])
    base["selection"] = selection

    evicted: list = []
    while True:
        with trace.span("plan.finalize", n=n):
            sched = finalize(cand, env, ncfg, flcfg, oma,
                             {**base, "evicted": list(evicted)})
        if t_budget <= 0 or sched.t_round <= t_budget or len(cand) <= 1:
            return sched
        # evict the latency-critical client, backfill the next
        # never-admitted client in priority order
        with trace.span("plan.evict", n=n):
            tot = (sched.t_cmp + sched.t_com) * sched.selected
            worst = int(np.argmax(tot))
            cand.remove(worst)
            evicted.append(worst)
            for nxt in order[slots:]:
                if (nxt not in cand and nxt not in evicted
                        and len(cand) < slots):
                    cand.append(int(nxt))
                    break


def plan_fixed(cand, env: RoundEnv, ncfg: NOMAConfig, flcfg: FLConfig, *,
               oma: bool = False, info: Optional[dict] = None,
               selection: Optional[str] = None) -> Schedule:
    """Pipeline for an explicitly chosen admitted set (random /
    round-robin drivers): optional joint refinement, then stages 3-5 (no
    budget loop — these policies never ran one)."""
    selection = check_choice(
        "selection", flcfg.selection if selection is None else selection)
    cand = [int(x) for x in cand]
    base = dict(info or {})
    if selection == "joint":
        with trace.span("plan.joint", n=len(env.gains)):
            cand = joint_admission(cand, env, ncfg, flcfg, oma=oma,
                                   diag=base)
    base["selection"] = selection
    with trace.span("plan.finalize", n=len(env.gains)):
        return finalize(cand, env, ncfg, flcfg, oma, base)


# ---------------------------------------------------------------------------
# multi-cell driver: partition by cell, run the staged pipeline per cell
# ---------------------------------------------------------------------------


def cell_capacity(n: int, n_cells: int, slots: int) -> int:
    """Static per-cell member capacity of the cell-partitioned planners.

    Both engines consider at most this many members per cell — the first
    ``cap`` in client-index order (a static-shape bound the jax engine can
    gather against; the numpy driver applies the identical truncation so
    the two can never disagree). ``2x`` the ceil-mean occupancy absorbs
    the multinomial imbalance of random placement at realistic N/C while
    staying O(N) total work; the ``2 * slots`` floor guarantees every cell
    can fill its subchannels even when the mean occupancy is tiny."""
    if n_cells <= 1:
        return n
    avg = -(-n // n_cells)
    return min(n, max(2 * avg, 2 * slots))


def plan_multicell(env: RoundEnv, cell: np.ndarray, n_cells: int,
                   ncfg: NOMAConfig, flcfg: FLConfig, *,
                   priority: np.ndarray, oma: bool = False,
                   info: Optional[dict] = None,
                   t_budget: Optional[float] = None,
                   selection: Optional[str] = None,
                   cap: Optional[int] = None) -> Schedule:
    """Cell-partitioned driver of the staged pipeline: each cell runs
    ``plan_round`` on its own members (frequency reuse 1 — every cell has
    the full K subchannels, and the round-time budget applies per cell
    since cells transmit in parallel), then the per-cell schedules merge
    into one client-space Schedule:

    * global round time = max over cells (the server waits for the slowest
      cell before aggregating);
    * aggregation weights pooled across cells (w_n = n_samples * selected
      / sum over ALL selected clients — one global FedAvg, not per-cell);
    * pair tables / eviction lists remapped to global client ids.

    ``n_cells <= 1`` delegates to ``plan_round`` unchanged (the C=1
    equivalence contract; engine twin: ``engine._multicell_schedule``).
    """
    if n_cells <= 1:
        return plan_round(env, ncfg, flcfg, priority=priority, oma=oma,
                          info=info, t_budget=t_budget, selection=selection)
    n = len(env.gains)
    slots = ncfg.n_subchannels * ncfg.users_per_subchannel
    cap = cell_capacity(n, n_cells, slots) if cap is None else cap
    cell = np.asarray(cell, dtype=int)
    priority = np.asarray(priority, dtype=np.float64)
    t_cmp = roundtime.compute_times(env.n_samples,
                                    flcfg.cpu_cycles_per_sample,
                                    env.cpu_freq, flcfg.local_epochs)
    selected = np.zeros(n, dtype=bool)
    rates = np.zeros(n)
    powers = np.zeros(n)
    pairs: list = []
    t_round = 0.0
    cells_info = []
    with trace.span("plan.multicell", n=n, n_cells=n_cells):
        for c in range(n_cells):
            members = np.flatnonzero(cell == c)[:cap]
            if members.size == 0:
                cells_info.append({"cell": c, "n_members": 0,
                                   "t_round": 0.0})
                continue
            sub_env = RoundEnv(gains=env.gains[members],
                               n_samples=env.n_samples[members],
                               cpu_freq=env.cpu_freq[members],
                               ages=env.ages[members],
                               model_bits=env.model_bits)
            sub = plan_round(sub_env, ncfg, flcfg,
                             priority=priority[members],
                             oma=oma, t_budget=t_budget,
                             selection=selection)
            selected[members] = sub.selected
            rates[members] = sub.rates
            powers[members] = sub.powers
            pairs += [(int(members[i]), int(members[j]) if j >= 0 else -1)
                      for (i, j) in sub.pairs]
            t_round = max(t_round, sub.t_round)
            cells_info.append({
                "cell": c, "n_members": int(members.size),
                "t_round": sub.t_round,
                "joint_swaps_accepted":
                    sub.info.get("joint_swaps_accepted", 0),
                "evicted": [int(members[e])
                            for e in sub.info.get("evicted", [])]})
    t_com = roundtime.comm_times(env.model_bits, rates)
    w = env.n_samples.astype(np.float64) * selected
    w = w / max(w.sum(), 1e-12)
    out_info = {**dict(info or {}),
                "selection": (flcfg.selection if selection is None
                              else selection),
                "n_cells": n_cells, "cell_cap": cap, "cells": cells_info}
    return Schedule(selected, pairs, rates, powers, t_cmp, t_com, t_round,
                    w, out_info)


# ---------------------------------------------------------------------------
# exhaustive references (tests / benchmarks)
# ---------------------------------------------------------------------------


def exhaustive_pairing_reference(cand, env: RoundEnv, ncfg: NOMAConfig,
                                 flcfg: FLConfig) -> float:
    """Optimal round time over ALL pairings of the candidate set (per-pair
    power allocation stays closed-form max-min, which is optimal for a fixed
    pair). Exponential — tests only (|cand| <= 8). The matching set comes
    from ``pairing.enumerate_matchings`` — the same (single) generator the
    hungarian policy's small-instance enumeration uses, so the two can
    never disagree on coverage or order."""
    cand = list(int(c) for c in cand)
    assert len(cand) % 2 == 0 and len(cand) <= 8
    t_cmp = roundtime.compute_times(env.n_samples,
                                    flcfg.cpu_cycles_per_sample,
                                    env.cpu_freq, flcfg.local_epochs)
    best = np.inf
    for rows in pairing.enumerate_matchings(len(cand) // 2):
        t_round = 0.0
        for (ia, ib) in rows:
            a, b = cand[ia], cand[ib]
            i, j = (a, b) if env.gains[a] >= env.gains[b] else (b, a)
            p_i, p_j = noma.pair_power_allocation(
                env.gains[i:i + 1], env.gains[j:j + 1], ncfg)
            r_i, r_j = noma.pair_rates(p_i, p_j, env.gains[i:i + 1],
                                       env.gains[j:j + 1], ncfg)
            t_round = max(t_round,
                          t_cmp[i] + env.model_bits / max(float(r_i[0]), 1e-9),
                          t_cmp[j] + env.model_bits / max(float(r_j[0]), 1e-9))
        best = min(best, t_round)
    return float(best)


def exhaustive_joint_reference(env: RoundEnv, ncfg: NOMAConfig,
                               flcfg: FLConfig, *, oma: bool = False,
                               n_admit: Optional[int] = None) -> float:
    """The exhaustive JOINT (set x matching) optimum: minimum round time
    over every size-``n_admit`` candidate set and every pairing of it —
    what ``selection="joint"`` must match on |N| <= JOINT_ENUM_MAX_N
    (pairing=hungarian realizes the optimal matching at these sizes).
    Exponential — tests/benchmarks only."""
    n = len(env.gains)
    assert n <= JOINT_ENUM_MAX_N, "exhaustive joint reference: |N| <= 8"
    slots = ncfg.n_subchannels * ncfg.users_per_subchannel
    c = min(slots, n) if n_admit is None else n_admit
    t_cmp = roundtime.compute_times(env.n_samples,
                                    flcfg.cpu_cycles_per_sample,
                                    env.cpu_freq, flcfg.local_epochs)
    return min(set_best_time(s, env, t_cmp, ncfg, oma=oma)
               for s in enumerate_subsets(n, c))
