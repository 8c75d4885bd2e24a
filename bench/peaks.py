"""Published peaks of each chip the benchmark runs on, keyed by JAX's
``device_kind``. A kind that is not in the table is an error, never a
default.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_per_s: float       # dense bf16 matrix-unit peak
    hbm_bytes_per_s: float   # HBM bandwidth
    hbm_bytes: float         # HBM capacity


PEAKS = {"TPU v5 lite": Peaks(197e12, 819e9, 16e9)}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None


def roofline_share(flops: float, bytes_: float, seconds: float,
                   device_kind: str):
    """Least time the chip could take for ``flops`` and ``bytes_`` (the
    larger of the two bounds) over the measured ``seconds``, in percent,
    with the name of the bound that applies."""
    pk = peaks(device_kind)
    t_flops = flops / pk.flops_per_s
    t_bytes = bytes_ / pk.hbm_bytes_per_s
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
