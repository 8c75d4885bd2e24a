"""Pure-jnp oracles for every Pallas kernel (the correctness references the
per-kernel allclose tests sweep against)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def weighted_sum_ref(updates, weights):
    """updates (C, N), weights (C,) -> (N,) fp32 weighted sum (HIGHEST:
    a TPU's default matmul precision would round the operands to bf16)."""
    return jnp.einsum("cn,c->n", updates.astype(jnp.float32),
                      weights.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def wkv6_ref(r, k, v, w_log, u, s0):
    """Naive RWKV6 recurrence. r,k,v,w_log (B,H,T,C); u (H,C); s0 (B,H,C,C).
    Returns (out (B,H,T,C) fp32, s_T).

        out_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)
        S_t   = diag(w_t) S_{t-1} + k_t v_t^T
    """
    rf = r.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    wf = jnp.exp(w_log.astype(jnp.float32))
    uf = u.astype(jnp.float32)

    def step(s, inp):
        rt, kt, vt, wt = inp            # (B,H,C)
        kv = kt[..., :, None] * vt[..., None, :]          # (B,H,C,C)
        out = jnp.einsum("bhc,bhcd->bhd", rt, s) \
            + jnp.einsum("bhc,hc,bhc,bhd->bhd", rt, uf, kt, vt)
        s_new = wt[..., None] * s + kv
        return s_new, out

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (rf, kf, vf, wf))
    s_t, outs = jax.lax.scan(step, s0.astype(jnp.float32), xs)
    return jnp.moveaxis(outs, 0, 2), s_t


def swa_ref(q, k, v, window: int, *, causal: bool = True):
    """Dense sliding-window attention oracle. q (B,S,H,hd), k/v (B,S,KH,hd).
    Position i attends j in (i-window, i]. Returns (B,S,H,hd) in q.dtype."""
    b, s, h, hd = q.shape
    kh = k.shape[2]
    g = h // kh
    qf = q.reshape(b, s, kh, g, hd).astype(jnp.float32) / jnp.sqrt(hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qf, k.astype(jnp.float32))
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    mask = (j > i - window)
    if causal:
        mask = mask & (j <= i)
    scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return out.reshape(b, s, h, hd).astype(q.dtype)
