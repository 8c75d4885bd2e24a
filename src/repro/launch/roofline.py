"""Roofline-term derivation from the compiled dry-run artifact.

Hardware constants come from ``CHIP_PEAKS``, keyed by
``jax.Device.device_kind``; a device that is not in the table is an error,
never a default.

``compiled.cost_analysis()`` reports PER-DEVICE flops/bytes, with every
``lax.scan`` body counted ONCE (verified empirically — see DESIGN.md
section 6). Corrections applied here:

  * flops/bytes: corrected = outer + trips x (raw - outer), where the
    outer (non-scanned) share is the analytic embed/head/loss flops and
    ``trips`` = layers x microbatches (x2 for the remat backward rescan
    being inside the same loop, already included in raw).
  * collectives: parsed from the compiled HLO text; every collective inside
    a while-body region is multiplied by the product of enclosing loop trip
    counts, which are recovered from the while-condition's comparison
    constant. Wire bytes use ring-collective formulas:
        all-gather / reduce-scatter : (g-1)/g x full
        all-reduce                  : 2 (g-1)/g x full
        all-to-all                  : (g-1)/g x full
        collective-permute          : full
"""
from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float        # bf16 FLOP/s per chip
    hbm_bw: float       # HBM bytes/s per chip
    ici_bw: float       # interconnect bytes/s per link


# Published per-chip peaks by ``device_kind``. "TPU v5 lite" is the TPU
# v5e (Google Cloud documentation, "TPU v5e"): 197 TFLOP/s bf16, 819 GB/s
# HBM, 1,600 Gbit/s of ICI per chip over four links (50 GB/s each).
CHIP_PEAKS = {"TPU v5 lite": ChipPeaks(197e12, 819e9, 50e9)}
# the chip the dry-run pods and the analytic kernel roofs are drawn for
TARGET_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; ValueError for a chip not in the table."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind "
                         f"{device_kind!r} (known: {sorted(CHIP_PEAKS)})"
                         ) from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"(\w[\w.-]*)\s*=\s*(?:\()?([a-z0-9]+)\[([\d,]*)\][^ ]*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))            # [ngroups, group_size]<=[N]
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return 1


def _wire_bytes(kind: str, nbytes: int, g: int) -> float:
    if g <= 1:
        return 0.0
    frac = (g - 1) / g
    if kind == "all-reduce":
        return 2.0 * nbytes * frac
    if kind in ("all-gather", "reduce-scatter", "all-to-all"):
        return nbytes * frac
    return float(nbytes)                   # collective-permute


@dataclasses.dataclass
class CollectiveStats:
    wire_bytes: float
    by_kind: dict
    count: int


def _parse_computations(hlo: str) -> dict:
    """Split HLO text into {computation_name: [lines]}."""
    comps: dict[str, list] = {}
    cur = None
    for line in hlo.splitlines():
        m = re.match(r"^\s*(?:ENTRY\s+)?%?([\w.-]+)\s+\(.*\)\s*->", line)
        if m:
            cur = m.group(1)
            comps[cur] = []
        elif cur is not None:
            comps[cur].append(line)
    return comps


def _while_trip_counts(comps: dict) -> dict:
    """Map body-computation name -> trip count, from while ops and their
    condition regions' comparison constants."""
    trips = {}
    cond_of = {}
    for cname, lines in comps.items():
        for line in lines:
            m = re.search(r"while\(.*?\).*?condition=%?([\w.-]+),\s*"
                          r"body=%?([\w.-]+)", line)
            if m:
                cond_of[m.group(2)] = m.group(1)
    for body, cond in cond_of.items():
        n = 1
        for line in comps.get(cond, []):
            mm = re.search(r"constant\((\d+)\)", line)
            if mm:
                n = max(n, int(mm.group(1)))
        trips[body] = n
    return trips


def _region_multipliers(comps: dict, trips: dict) -> dict:
    """Effective multiplier per computation = product of enclosing loop
    trips (nested whiles compose)."""
    # build call edges: computation -> regions it invokes via while body
    children = {c: [] for c in comps}
    for cname, lines in comps.items():
        for line in lines:
            m = re.search(r"body=%?([\w.-]+)", line)
            if m and m.group(1) in comps:
                children[cname].append(m.group(1))
            m2 = re.search(r"to_apply=%?([\w.-]+)", line)
            if m2 and m2.group(1) in comps:
                children[cname].append(m2.group(1))

    mult = {c: 1 for c in comps}

    def visit(c, factor, seen):
        if c in seen:
            return
        seen = seen | {c}
        mult[c] = max(mult[c], factor)
        for ch in children.get(c, []):
            f = factor * trips.get(ch, 1)
            visit(ch, f, seen)

    roots = [c for c in comps if "entry" in c.lower()
             or c.startswith("main")]
    if not roots:
        roots = list(comps)[:1]
    for r in roots:
        visit(r, 1, frozenset())
    # computations never reached keep multiplier >= their own trip product
    for body, t in trips.items():
        if mult.get(body, 1) == 1:
            mult[body] = t
    return mult


def collective_stats(hlo: str) -> CollectiveStats:
    comps = _parse_computations(hlo)
    trips = _while_trip_counts(comps)
    mult = _region_multipliers(comps, trips)
    total = 0.0
    by_kind: dict[str, float] = {}
    count = 0
    for cname, lines in comps.items():
        factor = mult.get(cname, 1)
        for line in lines:
            m = _COLL_RE.search(line)
            if not m:
                continue
            if m.group(5):  # -start op; the matching -done is not re-counted
                pass
            dtype, dims, kind = m.group(2), m.group(3), m.group(4)
            nbytes = _shape_bytes(dtype, dims)
            g = _group_size(line)
            wb = _wire_bytes(kind, nbytes, g) * factor
            total += wb
            by_kind[kind] = by_kind.get(kind, 0.0) + wb
            count += 1
    return CollectiveStats(wire_bytes=total, by_kind=by_kind, count=count)


# ---------------------------------------------------------------------------
# single-kernel roofline placement
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RoofPoint:
    """Placement of ONE kernel on the chip roofline: where its analytic
    arithmetic intensity (flop/byte) falls relative to the ridge point
    ``flops / hbm_bw`` of the chip and what fraction of peak the roof allows
    there. Shape-derived, not timed — the kernels_bench rows carry it so
    the roofline table can say WHY a kernel is bandwidth-bound."""
    flops: float
    bytes: float
    intensity: float         # flop / byte
    ridge: float             # peak flops / HBM bandwidth (flop/byte)
    bound: str               # "memory" when intensity < ridge else "compute"
    peak_fraction: float     # attainable FLOP/s at this intensity / peak
    t_compute: float         # seconds at peak compute
    t_memory: float          # seconds at peak HBM bandwidth


def kernel_roof_point(flops: float, bytes_: float,
                      device_kind: str) -> RoofPoint:
    """Place a kernel with analytic ``flops``/``bytes_`` on the roofline of
    ``device_kind`` (``chip_peaks``)."""
    peaks = chip_peaks(device_kind)
    peak_flops, hbm_bw = peaks.flops, peaks.hbm_bw
    intensity = flops / max(bytes_, 1.0)
    ridge = peak_flops / hbm_bw
    attainable = min(peak_flops, intensity * hbm_bw)
    return RoofPoint(
        flops=float(flops), bytes=float(bytes_), intensity=intensity,
        ridge=ridge, bound="memory" if intensity < ridge else "compute",
        peak_fraction=attainable / peak_flops,
        t_compute=flops / peak_flops, t_memory=bytes_ / hbm_bw)


# ---------------------------------------------------------------------------
# roofline assembly
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    # per-device raw
    hlo_flops_raw: float
    hlo_bytes_raw: float
    scan_factor: float
    # corrected per-device
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float      # per-device wire bytes
    # terms (seconds)
    t_compute: float
    t_memory: float          # from HLO bytes-accessed (op-level UPPER bound)
    t_memory_floor: float    # arguments+outputs touched once (LOWER bound)
    t_collective: float
    bottleneck: str
    model_flops: float           # analytic 6*N*D (global, whole step)
    useful_ratio: float          # model_flops / (hlo_flops * chips)
    memory_per_chip: float       # bytes (arguments+temp)
    note: str = ""

    def terms(self):
        return {"compute": self.t_compute, "memory": self.t_memory,
                "collective": self.t_collective}


def build_roofline(*, arch: str, shape: str, mesh_name: str, chips: int,
                   device_kind: str, cost: dict, mem, hlo: str,
                   scan_trips: int, outer_flops_per_dev: float,
                   model_flops: float, note: str = "") -> Roofline:
    peaks = chip_peaks(device_kind)
    raw_flops = float(cost.get("flops", 0.0))
    raw_bytes = float(cost.get("bytes accessed", 0.0))
    inner_f = max(raw_flops - outer_flops_per_dev, 0.0)
    flops = outer_flops_per_dev + scan_trips * inner_f
    scan_factor = flops / raw_flops if raw_flops else 1.0
    bytes_ = raw_bytes * scan_factor   # documented approximation
    colls = collective_stats(hlo)
    # nested scans (the flash-attention q/kv loops) are ALSO counted once by
    # HLO cost analysis; the analytic MODEL_FLOPS floor catches that
    # undercount, so the compute term takes the max of the two estimates.
    t_c = max(flops, model_flops / chips) / peaks.flops
    t_m = bytes_ / peaks.hbm_bw
    t_floor = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               - mem.alias_size_in_bytes) / peaks.hbm_bw
    t_l = colls.wire_bytes / peaks.ici_bw
    terms = {"compute": t_c, "memory": t_m, "collective": t_l}
    bott = max(terms, key=terms.get)
    mem_per_chip = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                    + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops_raw=raw_flops, hlo_bytes_raw=raw_bytes,
        scan_factor=scan_factor, hlo_flops=flops, hlo_bytes=bytes_,
        collective_bytes=colls.wire_bytes,
        t_compute=t_c, t_memory=t_m, t_memory_floor=t_floor,
        t_collective=t_l, bottleneck=bott,
        model_flops=model_flops,
        useful_ratio=(model_flops / (flops * chips)) if flops else 0.0,
        memory_per_chip=float(mem_per_chip), note=note)
