"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

Contractions run at ``Precision.HIGHEST``: on a TPU the default would
round float32 operands to bf16.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38
HI = jax.lax.Precision.HIGHEST


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        scale: Optional[float] = None) -> jax.Array:
    """q (B,S,H,hd); k/v (B,T,K,hd) with H % K == 0.  f32 accumulation."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    g = H // K
    scale = scale if scale is not None else hd ** -0.5
    qg = q.reshape(B, S, K, g, hd)
    s = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                   k.astype(jnp.float32), precision=HI) * scale
    qi = jnp.arange(S)[:, None]
    ki = jnp.arange(T)[None, :]
    ok = jnp.ones((S, T), bool)
    if causal:
        ok &= qi >= ki
    if window:
        ok &= (qi - ki) < window
    s = jnp.where(ok[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32),
                   precision=HI)
    return o.reshape(B, S, H, hd).astype(q.dtype)


def decode_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                         lengths: jax.Array, *,
                         scale: Optional[float] = None) -> jax.Array:
    """q (B,H,hd); k/v (B,T,K,hd); lengths (B,) valid prefix per row."""
    B, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    g = H // K
    scale = scale if scale is not None else hd ** -0.5
    qg = q.reshape(B, K, g, hd)
    s = jnp.einsum("bkgd,btkd->bkgt", qg.astype(jnp.float32),
                   k.astype(jnp.float32), precision=HI) * scale
    ok = jnp.arange(T)[None, :] < lengths[:, None]
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,btkd->bkgd", p, v.astype(jnp.float32),
                   precision=HI)
    return o.reshape(B, H, hd).astype(q.dtype)


def wkv_ref(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
            u: jax.Array) -> jax.Array:
    """Sequential RWKV6 recurrence oracle.  r/k/v/w (B,T,H,hs); u (H,hs).

    S_t = diag(w_t) S_{t-1} + k_t^T v_t ;  y_t = r_t (S_{t-1} + u k_t^T v_t)
    """
    B, T, H, hs = r.shape
    rf = r.astype(jnp.float32).transpose(1, 0, 2, 3)
    kf = k.astype(jnp.float32).transpose(1, 0, 2, 3)
    vf = v.astype(jnp.float32).transpose(1, 0, 2, 3)
    wf = w.astype(jnp.float32).transpose(1, 0, 2, 3)
    uf = u.astype(jnp.float32)

    def step(S, inp):
        rt, kt, vt, wt = inp
        kv = kt[..., :, None] * vt[..., None, :]
        y = jnp.einsum("bhi,bhij->bhj", rt, S + uf[None, :, :, None] * kv,
                       precision=HI)
        S = S * wt[..., :, None] + kv
        return S, y

    S0 = jnp.zeros((B, H, hs, hs), jnp.float32)
    _, ys = jax.lax.scan(step, S0, (rf, kf, vf, wf))
    return ys.transpose(1, 0, 2, 3).astype(r.dtype)


def grouped_gemm_ref(x: jax.Array, w: jax.Array,
                     group_sizes: jax.Array) -> jax.Array:
    """x (E,C,din); w (E,din,dout); rows >= group_sizes[e] are masked to 0."""
    E, C, _ = x.shape
    y = jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32),
                   w.astype(jnp.float32), precision=HI)
    mask = jnp.arange(C)[None, :] < group_sizes[:, None]
    return (y * mask[..., None]).astype(x.dtype)
