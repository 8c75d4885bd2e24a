"""Multi-cell topology: base-station layouts and nearest-BS association.

The multi-cell hierarchy (DESIGN.md section 10) places ``n_cells`` base
stations on a deterministic layout — a hex spiral or a square grid, both
with inter-BS spacing ``sqrt(3) * cell_radius_m`` (the hex-packing distance
at which circumradius-R cells tile without gaps) — and derives each
client's serving cell from its position as the nearest BS (Voronoi
association). Mobility that moves a client across a Voronoi boundary is a
handover: only the association index changes, the client's age/selection
state rides along untouched.

Layouts are host-side fp64 numpy, cached per ``(n_cells, layout, radius)``
and byte-frozen; the jit'ed scenario step bakes them in as constants.
``n_cells == 1`` collapses to one BS at the origin so every multi-cell
formula degenerates to the legacy single-cell geometry.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

from repro.configs.base import FLConfig, NOMAConfig, check_choice

__all__ = ["CellTopology", "bs_layout", "region_radius", "nearest_cell"]


@functools.lru_cache(maxsize=None)
def bs_layout(n_cells: int, layout: str, cell_radius_m: float) -> np.ndarray:
    """Deterministic ``(n_cells, 2)`` fp64 BS coordinates (read-only).

    Candidate sites are enumerated out to a ring/box that provably holds
    ``n_cells`` points, then taken in ``(distance-from-origin, angle)``
    order so prefixes nest: the first C sites of a (C+1)-cell layout are
    the C-cell layout.
    """
    if n_cells < 1:
        raise ValueError(f"n_cells must be >= 1, got {n_cells}")
    check_choice("cell_layout", layout)
    if n_cells == 1:
        pts = np.zeros((1, 2))
    elif layout == "hex":
        k = 0
        while 3 * k * (k + 1) + 1 < n_cells:
            k += 1
        d = np.sqrt(3.0) * cell_radius_m
        sites = []
        for q in range(-k, k + 1):
            for r in range(-k, k + 1):
                if max(abs(q), abs(r), abs(-q - r)) <= k:
                    sites.append((d * (q + 0.5 * r),
                                  d * (np.sqrt(3.0) / 2.0) * r))
        pts = _closest_first(np.array(sites))[:n_cells]
    else:  # grid
        k = int(np.ceil(np.sqrt(n_cells)))
        d = np.sqrt(3.0) * cell_radius_m
        ij = np.arange(k, dtype=np.float64) - (k - 1) / 2.0
        xx, yy = np.meshgrid(ij * d, ij * d, indexing="ij")
        pts = _closest_first(np.stack([xx.ravel(), yy.ravel()],
                                      axis=-1))[:n_cells]
    pts = np.ascontiguousarray(pts)
    pts.flags.writeable = False
    return pts


def _closest_first(pts: np.ndarray) -> np.ndarray:
    """Order sites by (rounded distance, angle) — rounding makes same-ring
    ties resolve by angle instead of fp noise, so the order is stable."""
    dist = np.hypot(pts[:, 0], pts[:, 1])
    ang = np.arctan2(pts[:, 1], pts[:, 0])
    return pts[np.lexsort((ang, np.round(dist, 6)))]


def region_radius(n_cells: int, layout: str, cell_radius_m: float) -> float:
    """Outer reflection radius of the whole deployment: the farthest BS
    plus one cell radius. Equals ``cell_radius_m`` when ``n_cells == 1``."""
    bs = bs_layout(n_cells, layout, cell_radius_m)
    return float(np.linalg.norm(bs, axis=-1).max()) + cell_radius_m


def nearest_cell(pos, bs, xp=np):
    """Voronoi association: ``(cell, dist)`` of the nearest BS.

    ``pos`` is ``(..., 2)``, ``bs`` is ``(C, 2)``; works for numpy and
    jax.numpy alike (``xp`` picks the namespace). ``dist`` is the true
    distance to the serving BS — callers floor it at ``min_radius_m``
    for path loss, exactly as the single-cell path does.
    """
    d2 = ((pos[..., None, :] - bs) ** 2).sum(-1)
    cell = xp.argmin(d2, axis=-1)
    d2c = xp.take_along_axis(d2, cell[..., None], axis=-1)[..., 0]
    return cell.astype(xp.int32), xp.sqrt(d2c)


@dataclasses.dataclass(frozen=True)
class CellTopology:
    """Resolved multi-cell geometry (layout + radii), the config-facing
    companion of ``FLConfig.n_cells``/``cell_layout``."""

    n_cells: int = 1
    layout: str = "hex"
    cell_radius_m: float = 500.0
    min_radius_m: float = 50.0

    def __post_init__(self) -> None:
        if self.n_cells < 1:
            raise ValueError(f"n_cells must be >= 1, got {self.n_cells}")
        check_choice("cell_layout", self.layout)
        if self.min_radius_m < 0.0 or self.min_radius_m >= self.cell_radius_m:
            raise ValueError(
                f"need 0 <= min_radius_m < cell_radius_m, got "
                f"({self.min_radius_m}, {self.cell_radius_m})")

    @classmethod
    def from_configs(cls, ncfg: NOMAConfig, flcfg: FLConfig) -> "CellTopology":
        return cls(n_cells=flcfg.n_cells, layout=flcfg.cell_layout,
                   cell_radius_m=ncfg.cell_radius_m,
                   min_radius_m=ncfg.min_radius_m)

    @property
    def bs_xy(self) -> np.ndarray:
        return bs_layout(self.n_cells, self.layout, self.cell_radius_m)

    @property
    def region_radius_m(self) -> float:
        return region_radius(self.n_cells, self.layout, self.cell_radius_m)

    def cell_of(self, pos, xp=np):
        return nearest_cell(pos, xp.asarray(self.bs_xy), xp=xp)
