"""Operations and bytes of the FedAvg weighted sum ``out[n] = sum_c w[c]
u[c, n]`` over the logical (C, N) problem of one parameter leaf: one
multiply and one add per element, each fp32 update read once, the weights
read once and the fp32 sum written once. Tiles and padding are not
counted."""
from __future__ import annotations


def flops(c: int, n: int) -> float:
    return 2.0 * c * n


def bytes_(c: int, n: int, itemsize: int = 4) -> float:
    return float(c * n * itemsize + c * 4 + n * 4)
