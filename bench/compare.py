"""The comparisons that decide ``correct``, shared by the drivers: each
number is judged against a limit of its own, taken from the cell's file."""
from __future__ import annotations

import math

import numpy as np

# a leaf whose reference norm is under this share of the median leaf's
# moves by round-off alone and is not compared
TINY_LEAF = 1e-3


def norm_gap(prog, ref) -> float:
    """Worst leaf of |‖prog‖ - ‖ref‖| over max(‖ref‖, median leaf ‖ref‖),
    for two equal-length sequences of per-leaf norms."""
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    med = float(np.median(ref))
    keep = ref >= TINY_LEAF * med
    if not np.all(np.isfinite(prog)):
        return math.inf
    gap = np.abs(prog - ref) / np.maximum(ref, med)
    return float(np.max(gap[keep])) if np.any(keep) else 0.0


def max_rel(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return math.inf
    if got.size == 0:
        return 0.0
    if not np.all(np.isfinite(got)):
        return math.inf
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-30)))


def judge(numbers: dict, limits: dict) -> list:
    """[(name, value, limit, ok)] in the order of ``limits``."""
    out = []
    for name, limit in limits.items():
        v = float(numbers.get(name, math.inf))
        out.append((name, v, float(limit), bool(v <= limit)))
    return out


def finite(obj):
    """``obj`` with every non-finite float written as a string, for JSON."""
    if isinstance(obj, dict):
        return {k: finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [finite(v) for v in obj]
    if isinstance(obj, float) and not math.isfinite(obj):
        return str(obj)
    return obj
