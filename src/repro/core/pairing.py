"""Subchannel pairing policies (numpy fp64 reference).

The paper's heuristic pairs the i-th strongest candidate with the i-th
weakest (``strong_weak``). This module generalizes pairing into a policy
interface over the *pair score table* (DESIGN.md section 7):

    score[p, j] = min SIC rate of (strong-half rank p, weak-half pos j)
                  under closed-form max-min power  (``min_rate_table``)
    cost[p, j]  = the pair's completion time
                  max(T_cmp,p + S/R_i, T_cmp,j + S/R_j)  (``completion_table``)

Policies (``FLConfig.pairing``):

    strong_weak      reversal pairing — provably maximizes the bottleneck
                     min-rate over the half-split (the min-rate is
                     ``f(min(y*(g_i), P g_j))`` with f increasing, so every
                     half-split matching shares the same bottleneck);
    adjacent         neighbouring sorted gains — the NOMA worst case
                     (similar gains), kept as an ablation axis;
    hungarian        exact min-sum assignment of weak users to strong users
                     on the completion-time table (shortest augmenting path,
                     O(m^3)), followed by a deterministic bottleneck 2-opt
                     pass over the full sorted-rank table (the half-split
                     is bottleneck-optimal for *comm* time but heterogeneous
                     T_cmp can favour same-half pairs — the 2-opt explores
                     them), and a never-slower guard: if the result's worst
                     pair completion is not strictly better than
                     strong_weak's, the heuristic is kept — so hungarian is
                     never slower than strong_weak in round time by
                     construction;
    greedy_matching  repeatedly take the highest-scoring available
                     (strong, weak) pair from the effective-power table —
                     the strictly monotone min-rate surrogate whose
                     structural ties are precision-exact
                     (``effective_power_table``).

Both matching policies operate on the gain-sorted half-split (top half
strong, bottom half weak), which contains every bottleneck-optimal
matching (any pairing that makes a below-median client the strong user
can only lower the bottleneck min-rate — see DESIGN.md 7.2).

The batched jit/vmap-able device twins live in ``core/matching.py``; this
module is the fp64 semantic reference the parity tier pins against.
"""
from __future__ import annotations

import functools

import numpy as np

from repro.configs.base import NOMAConfig, check_choice
from repro.core import noma

# m <= this: the hungarian policy solves the bottleneck exactly by
# enumerating all perfect matchings (15 at m=3, 105 at m=4) — 2-opt has
# local optima there while enumeration is cheaper than the assignment
# solve itself; it also makes the policy provably optimal on every
# instance the exhaustive C4 reference can check (|cand| <= 8)
ENUM_MAX_PAIRS = 4


# ---------------------------------------------------------------------------
# score tables
# ---------------------------------------------------------------------------


def min_rate_table(g_strong: np.ndarray, g_weak: np.ndarray,
                   ncfg: NOMAConfig) -> np.ndarray:
    """(len(g_strong), len(g_weak)) pair score table: min SIC rate under
    closed-form max-min power (numpy twin of
    ``kernels.ops.pair_score_matrix``; DESIGN.md 7.1). NOT greedy's score
    surface — policies that argmax over the table must use
    ``effective_power_table``, whose structural ties survive fp32."""
    gi = np.asarray(g_strong, np.float64)[:, None]
    gj = np.asarray(g_weak, np.float64)[None, :]
    return noma.pair_min_rate(gi, gj, ncfg)


def effective_power_table(g_strong: np.ndarray, g_weak: np.ndarray,
                          ncfg: NOMAConfig) -> np.ndarray:
    """min(y*(g_i), P g_j): the pair's effective weak received power — a
    strictly monotone surrogate of the min-rate score (min-rate is
    ``B log1p(. / N0B)`` of it). The greedy policy scores on THIS table:
    its ties are structural (a row cap or a column cap binding twice) and
    stay bit-exact in fp32 and fp64, so greedy's argmax tie-breaks agree
    between the numpy reference and the engine — scoring on the min-rate
    itself reintroduces per-cell rounding that splits those ties
    differently per precision (DESIGN.md 7.2)."""
    n0b = noma.noise_power(ncfg)
    pmax = ncfg.max_power_w
    g_i = np.asarray(g_strong, np.float64)
    y = 0.5 * (-n0b + np.sqrt(n0b ** 2 + 4.0 * pmax * g_i * n0b))
    return np.minimum(y[:, None],
                      pmax * np.asarray(g_weak, np.float64)[None, :])


def completion_table(g_strong: np.ndarray, g_weak: np.ndarray,
                     t_cmp_strong: np.ndarray, t_cmp_weak: np.ndarray,
                     model_bits: float, ncfg: NOMAConfig,
                     oma: bool = False) -> np.ndarray:
    """(m, m) pair completion-time table: the round-time contribution of
    pairing strong p with weak j — ``max`` over the two users of
    ``T_cmp + S / R`` with the per-user SIC (or OMA-ablation) rates."""
    gi = np.asarray(g_strong, np.float64)[:, None]
    gj = np.asarray(g_weak, np.float64)[None, :]
    if oma:
        pmax = np.full_like(gi + gj, ncfg.max_power_w)
        r_i, r_j = noma.oma_pair_rates(pmax, pmax, gi, gj, ncfg)
    else:
        p_i, p_j = noma.pair_power_allocation(gi, gj, ncfg)
        r_i, r_j = noma.pair_rates(p_i, p_j, gi, gj, ncfg)
    t_i = np.asarray(t_cmp_strong)[:, None] + model_bits / np.maximum(
        r_i, 1e-9)
    t_j = np.asarray(t_cmp_weak)[None, :] + model_bits / np.maximum(
        r_j, 1e-9)
    return np.maximum(t_i, t_j)


# ---------------------------------------------------------------------------
# assignment solvers (fp64 reference; jax twins in core/matching.py)
# ---------------------------------------------------------------------------


def hungarian_assignment(cost: np.ndarray) -> np.ndarray:
    """Exact min-sum square assignment via shortest augmenting paths with
    dual potentials (O(m^3)). Returns ``col4row``: row p is assigned column
    ``col4row[p]``. Ties in the Dijkstra column scan resolve to the lowest
    index — the jax twin (``core.matching``) is a literal transcription, so
    the two implementations agree up to fp32-vs-fp64 cost rounding."""
    cost = np.asarray(cost, np.float64)
    m = cost.shape[0]
    u = np.zeros(m)
    v = np.zeros(m)
    col4row = np.full(m, -1, np.int64)
    row4col = np.full(m, -1, np.int64)
    for cur_row in range(m):
        shortest = np.full(m, np.inf)
        path = np.full(m, -1, np.int64)
        scanned_r = np.zeros(m, bool)
        scanned_c = np.zeros(m, bool)
        i = cur_row
        min_val = 0.0
        sink = -1
        while sink < 0:
            scanned_r[i] = True
            red = min_val + cost[i] - u[i] - v
            upd = ~scanned_c & (red < shortest)
            shortest[upd] = red[upd]
            path[upd] = i
            masked = np.where(scanned_c, np.inf, shortest)
            j = int(np.argmin(masked))
            min_val = float(masked[j])
            scanned_c[j] = True
            if row4col[j] < 0:
                sink = j
            else:
                i = int(row4col[j])
        # dual update
        u[cur_row] += min_val
        other = np.flatnonzero(scanned_r & (np.arange(m) != cur_row))
        u[other] += min_val - shortest[col4row[other]]
        v[scanned_c] -= min_val - shortest[scanned_c]
        # augment along the alternating path
        j = sink
        while True:
            i = int(path[j])
            row4col[j] = i
            col4row[i], j = j, int(col4row[i])
            if i == cur_row:
                break
    return col4row


def greedy_assignment(score: np.ndarray) -> np.ndarray:
    """Greedy max-score matching: repeatedly take the highest-scoring
    (row, col) among unmatched rows/columns (ties: first in row-major
    order, matching ``jnp.argmax``). Returns ``col4row``."""
    score = np.asarray(score, np.float64)
    m = score.shape[0]
    col4row = np.full(m, -1, np.int64)
    avail_r = np.ones(m, bool)
    avail_c = np.ones(m, bool)
    for _ in range(m):
        masked = np.where(avail_r[:, None] & avail_c[None, :], score,
                          -np.inf)
        p, j = divmod(int(np.argmax(masked)), m)
        col4row[p] = j
        avail_r[p] = False
        avail_c[j] = False
    return col4row


@functools.lru_cache(maxsize=None)
def enumerate_matchings(m: int) -> np.ndarray:
    """All perfect matchings of ``range(2m)`` as an (L, m, 2) int array,
    pairs normalized (lo, hi). The recursive generation order is the
    shared deterministic tie-break between the numpy and jax enumeration
    paths (argmin takes the first optimum)."""
    def rec(items):
        if not items:
            return [[]]
        a, out = items[0], []
        for i in range(1, len(items)):
            rest = items[1:i] + items[i + 1:]
            out += [[(a, items[i])] + sub for sub in rec(rest)]
        return out

    return np.array(rec(list(range(2 * m))),
                    dtype=np.int64).reshape(-1, max(m, 0), 2)


def exhaustive_bottleneck(table: np.ndarray, m: int
                          ) -> tuple[np.ndarray, np.ndarray]:
    """Exact min-max pairing of ranks 0..2m-1 over the completion table by
    enumeration (tiny m only; L = 1, 3, 15, 105 for m = 1..ENUM_MAX_PAIRS)."""
    mt = enumerate_matchings(m)
    vals = table[mt[:, :, 0], mt[:, :, 1]]          # (L, m)
    best = int(np.argmin(vals.max(axis=1)))
    return mt[best, :, 0], mt[best, :, 1]


def two_opt_refine(table: np.ndarray, strong_pos: np.ndarray,
                   weak_pos: np.ndarray, sweeps: int = 2
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Bottleneck 2-opt over a full (c, c) pair completion table indexed by
    sorted rank (row = strong = lower rank). For every pair of pairs the
    two re-pairings are tried and adopted when they strictly lower the max
    of the two completions (ties keep the current pairing; equal
    alternatives prefer the first) — a fixed ``sweeps``-pass deterministic
    schedule, transcribed identically in ``core.matching``."""
    a = np.asarray(strong_pos).copy()
    b = np.asarray(weak_pos).copy()
    m = len(a)
    for _ in range(sweeps):
        for x in range(m):
            for y in range(x + 1, m):
                pa, pb, qa, qb = a[x], b[x], a[y], b[y]
                cur = max(table[pa, pb], table[qa, qb])
                c1 = (min(pa, qa), max(pa, qa)), (min(pb, qb), max(pb, qb))
                c2 = (min(pa, qb), max(pa, qb)), (min(pb, qa), max(pb, qa))
                alt1 = max(table[c1[0]], table[c1[1]])
                alt2 = max(table[c2[0]], table[c2[1]])
                if alt1 < cur and alt1 <= alt2:
                    (a[x], b[x]), (a[y], b[y]) = c1
                elif alt2 < cur:
                    (a[x], b[x]), (a[y], b[y]) = c2
    return a, b


# ---------------------------------------------------------------------------
# the policy interface
# ---------------------------------------------------------------------------


def pair_candidates(gains: np.ndarray, cand: np.ndarray, policy: str, *,
                    t_cmp: np.ndarray | None = None,
                    model_bits: float | None = None,
                    ncfg: NOMAConfig | None = None,
                    oma: bool = False) -> list[tuple[int, int]]:
    """Partition an even-sized candidate set into (strong, weak) SIC pairs
    under ``policy``. Candidates sort by (gain desc, client index asc) —
    the same total order as the engine's bitonic argsort — so ties are
    deterministic and engine-consistent."""
    check_choice("pairing", policy)
    cand = np.asarray(cand, dtype=int)
    assert len(cand) % 2 == 0, "pair_candidates needs an even candidate set"
    order = noma.pairing_order(gains, cand)
    m = len(order) // 2
    if m == 0:
        return []
    if policy == "strong_weak":
        return noma.strong_weak_pairing(gains, cand)
    if policy == "adjacent":
        return noma.adjacent_pairing(gains, cand)
    strong, weak = order[:m], order[m:]
    if policy == "greedy_matching":
        sigma = greedy_assignment(
            effective_power_table(gains[strong], gains[weak], ncfg))
    elif policy == "hungarian":
        if t_cmp is None or model_bits is None:
            raise ValueError("hungarian pairing needs t_cmp + model_bits")
        # full sorted-rank completion table; the half-split slice
        # [0:m, m:2m] is the assignment cost, the whole table feeds the
        # bottleneck refinement (DESIGN.md 7.2)
        table = completion_table(gains[order], gains[order], t_cmp[order],
                                 t_cmp[order], model_bits, ncfg, oma=oma)
        rows = np.arange(m)
        rev = np.arange(2 * m - 1, m - 1, -1)
        if m <= ENUM_MAX_PAIRS:
            a, b = exhaustive_bottleneck(table, m)
        else:
            # min-sum assignment init + deterministic multi-start 2-opt
            # (strong_weak / adjacent restarts escape local optima)
            sigma = hungarian_assignment(table[:m, m:])
            best_t, a, b = np.inf, rows, rev
            for a0, b0 in ((rows, m + sigma), (rows, rev),
                           (2 * rows, 2 * rows + 1)):
                ca, cb = two_opt_refine(table, a0, b0)
                t = table[ca, cb].max()
                if t < best_t:
                    best_t, a, b = t, ca, cb
        # never-slower guard: keep the heuristic unless the refined
        # pairing's worst completion strictly improves on strong_weak's
        if table[a, b].max() >= table[rows, rev].max():
            a, b = rows, rev
        return [(int(order[a[p]]), int(order[b[p]])) for p in range(m)]
    return [(int(strong[p]), int(weak[sigma[p]])) for p in range(m)]
