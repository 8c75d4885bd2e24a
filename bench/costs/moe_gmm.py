"""Operations and bytes of the held experts' grouped products in one MoE
layer's SGD step, on the logical problem: the forward gate, up and down
products and, for each, the backward products for its input rows and for
its weights, over the (token, choice) pairs routed to held experts. Each
of the nine products reads or writes the held experts' fp32 weights (or
their gradients) once, and reads and writes its rows once. The sorted
buffer's padding rows and the dispatch are not counted.
"""
from __future__ import annotations


def flops(d: int, f: int, routed: float) -> float:
    """9 products of 2 x d x f FLOPs per routed pair."""
    return 18.0 * d * f * routed


def bytes_(d: int, f: int, held: int, routed: float,
           itemsize: int = 4) -> float:
    """9 x (the held weights, and each routed pair's d + f row values)."""
    return 9.0 * itemsize * (held * d * f + routed * (d + f))
