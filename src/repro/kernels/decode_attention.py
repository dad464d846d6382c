"""FlashDecode Pallas kernel: one query token vs a long KV cache.

Grid: (B*K, nk) — per (batch, kv-head) the kernel streams (bk, hd) KV tiles
sequentially with online-softmax state in VMEM; all G = H/K query heads of
the group are processed together as a (G, hd) q tile (so the KV tile is
read once per group — the GQA arithmetic-intensity win).  Per-row `lengths`
masks ring-buffer slots beyond the valid prefix.

Decode is KV-bandwidth bound; the roofline win vs the XLA path is reading
the KV cache exactly once at bf16 instead of materializing f32 scores.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import dot_precision

NEG_INF = -1.0e38


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale: float, bk: int, nk: int, precision):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid_len = len_ref[pl.program_id(0)]
    need = (ik * bk) < valid_len

    @pl.when(need)
    def _compute():
        v = v_ref[...]                                # (bk, hd)
        s = jax.lax.dot_general(q_ref[...], k_ref[...],   # (G,hd).(bk,hd)
                                (((1,), (1,)), ((), ())),
                                precision=precision,
                                preferred_element_type=jnp.float32) * scale
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < valid_len, s, NEG_INF)
        m_prev = m_ref[...]                           # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(m_new == NEG_INF, 0.0, p)
        alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_new))
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[...]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, *, bk: int = 256,
                     interpret: bool = False) -> jax.Array:
    """q (B,H,hd); k/v (B,T,K,hd); lengths (B,) int32.  -> (B,H,hd)."""
    B, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    g = H // K
    scale = hd ** -0.5
    bk = min(bk, max(T, 8))
    Tp = math.ceil(T / bk) * bk
    nk = Tp // bk

    qr = q.reshape(B, K, g, hd).reshape(B * K, g, hd)
    kr = jnp.pad(k, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    vr = jnp.pad(v, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    kr = kr.transpose(0, 2, 1, 3).reshape(B * K, Tp, hd)
    vr = vr.transpose(0, 2, 1, 3).reshape(B * K, Tp, hd)
    # per-(batch, kv-head) valid lengths ride in SMEM as scalar prefetch
    lens = jnp.repeat(lengths.astype(jnp.int32), K)

    kernel = functools.partial(_decode_kernel, scale=scale, bk=bk, nk=nk,
                               precision=dot_precision(q.dtype))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * K, nk),
            in_specs=[
                pl.BlockSpec((None, g, hd), lambda bh, ik, lens: (bh, 0, 0)),
                pl.BlockSpec((None, bk, hd), lambda bh, ik, lens: (bh, ik, 0)),
                pl.BlockSpec((None, bk, hd), lambda bh, ik, lens: (bh, ik, 0)),
            ],
            out_specs=pl.BlockSpec((None, g, hd),
                                   lambda bh, ik, lens: (bh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * K, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lens, qr, kr, vr)
    return out.reshape(B, K, g, hd).reshape(B, H, hd)
