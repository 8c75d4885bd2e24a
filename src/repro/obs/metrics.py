"""Round metrics: the shared diag-leaf definitions both engines implement
(DESIGN.md section 11).

The AoU histogram bucket edges (``AOU_BUCKET_EDGES``) and the numpy
bucketizer (``aou_histogram``) whose jax twin lives in ``core/engine.py``
(``engine.schedule_diag``), kept here so the two bucketings can never
disagree.

``json_safe`` is the ONE non-finite/ndarray scrubbing rule shared by
``History.as_dict``, the MC summaries, and the JSONL ledger: ndarrays
become lists, numpy scalars become Python scalars, non-finite floats
become ``None`` (bare NaN tokens break strict JSON parsers).
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

__all__ = ["AOU_BUCKET_EDGES", "aou_histogram", "json_safe"]

# AoU histogram bucket upper edges (ages are integers >= 1): bucket i
# counts ages in (edge[i-1], edge[i]], the last bucket counts > edge[-1].
# Doubling edges track the staleness tail the paper's fairness claim is
# about without a per-config bucket choice.
AOU_BUCKET_EDGES = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0)


def aou_histogram(ages, edges: Sequence[float] = AOU_BUCKET_EDGES
                  ) -> np.ndarray:
    """Fixed-shape AoU bucket counts (numpy reference; jax twin:
    ``engine._aou_histogram``). ``ages`` (..., N) -> int64 counts
    (..., len(edges) + 1); bucket i is ages in (edges[i-1], edges[i]],
    the final bucket is ages > edges[-1]."""
    ages = np.asarray(ages, dtype=np.float64)
    e = np.asarray(edges, dtype=np.float64)
    idx = np.searchsorted(e, ages, side="left")   # a <= e[i] -> bucket i
    k = len(e) + 1
    one_hot = idx[..., None] == np.arange(k)
    return one_hot.sum(axis=-2).astype(np.int64)


def json_safe(v):
    """Recursively convert ``v`` to strict-JSON-safe types: ndarrays and
    jax arrays -> (nested) lists, numpy scalars -> Python scalars,
    non-finite floats -> None, dict keys -> str. Dataclasses pass through
    ``dataclasses.asdict``."""
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        return json_safe(dataclasses.asdict(v))
    if isinstance(v, dict):
        return {str(k): json_safe(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [json_safe(x) for x in v]
    if isinstance(v, np.ndarray):
        return json_safe(v.tolist())
    if hasattr(v, "__jax_array__") or type(v).__name__ == "ArrayImpl":
        return json_safe(np.asarray(v).tolist())
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        v = float(v)
    if isinstance(v, float):
        return v if np.isfinite(v) else None
    if isinstance(v, (bool, int, str)) or v is None:
        return v
    return str(v)

