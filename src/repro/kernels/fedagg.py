"""Pallas TPU kernel: age/size-weighted aggregation of stacked client
updates — the FedAvg sum on the explicit ``pallas`` path (the default
aggregation is the fused sum of ``fl.aggregate``, which needs no stack).

    out[n] = sum_c w[c] * updates[c, n]

Arithmetic intensity is ~1 flop/byte, so the design is BANDWIDTH-oriented
(DESIGN.md section 3): the grid walks (N tiles, C tiles) with the C axis
innermost, each step multiplying a (bc, bn) block by its weight column on
the VPU in exact fp32 and accumulating the sublane sum into the revisited
(1, bn) output block. ``block_shape`` sizes the block from C and the
update dtype so that the double-buffered pipeline fits the scoped VMEM of
a v5e for any cohort the FL path can produce; ragged edges in N and C are
handled in place (no padded copy of the C x N updates).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
BLOCK_N = 65_536        # upper bound on the N tile (fp32 elements)
C_TILE = 128            # C rows per step once C exceeds it
# bytes of ONE (bc, bn) input block: double-buffered plus its fp32 product
# it stays under v5e's 16 MiB scoped VMEM default
BLOCK_BYTES = 2 << 20


def block_shape(c: int, n: int, itemsize: int, block_n: int = BLOCK_N):
    """(bc, bn) tile for ``c`` updates of ``n`` elements each: bc = c up to
    ``C_TILE`` rows, bn a multiple of 128 lanes within ``BLOCK_BYTES`` and
    ``block_n`` (or the whole of a shorter N)."""
    bc = c if c <= C_TILE else C_TILE
    bn = BLOCK_BYTES // (bc * max(itemsize, 4))
    bn = max(LANES, min(block_n, bn) // LANES * LANES)
    return bc, (n if n <= bn else bn)


def _fedagg_kernel(w_ref, u_ref, o_ref, *, c):
    # w_ref (bc, 1) fp32; u_ref (bc, bn); o_ref (1, bn) fp32
    k = pl.program_id(1)
    bc = u_ref.shape[0]
    prod = w_ref[...] * u_ref[...].astype(jnp.float32)
    if c % bc:  # ragged last C tile: rows past c hold unspecified data
        row = k * bc + jax.lax.broadcasted_iota(jnp.int32, (bc, 1), 0)
        prod = jnp.where(row < c, prod, 0.0)
    part = jnp.sum(prod, axis=0, keepdims=True)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = part

    @pl.when(k > 0)
    def _acc():
        o_ref[...] += part


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def fedagg_pallas(updates, weights, *, block_n: int = BLOCK_N,
                  interpret: bool = False):
    """updates (C, N) any float dtype; weights (C,) fp32 -> (N,) fp32."""
    c, n = updates.shape
    bc, bn = block_shape(c, n, updates.dtype.itemsize, block_n)
    out = pl.pallas_call(
        functools.partial(_fedagg_kernel, c=c),
        grid=(pl.cdiv(n, bn), pl.cdiv(c, bc)),
        in_specs=[
            pl.BlockSpec((bc, 1), lambda i, k: (k, 0)),
            pl.BlockSpec((bc, bn), lambda i, k: (k, i)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
        name="fedagg",
    )(weights.astype(jnp.float32).reshape(c, 1), updates)
    return out[0]
