"""FlashAttention forward Pallas kernel (TPU target, GQA, causal/windowed).

Grid: (B*H, nq, nk) — the innermost kv dimension is sequential on TPU, so
the online-softmax running state (m, l, acc) lives in VMEM scratch and is
carried across kv steps.  BlockSpecs stream one (bq, hd) query tile and one
(bk, hd) KV tile into VMEM per step; GQA maps query head h to KV head
h // (H // K) in the index maps, so KV tiles are fetched once per group.

The dots take the operands in their own dtype with float32 accumulation.
VMEM working set per step: bq*hd (q) + 2*bk*hd (kv) + bq*hd f32 (acc)
+ (bq, 1) f32 softmax stats (2-D: Mosaic has no 1-D vector layout) —
with bq=bk=128, hd<=256 this is < 0.5 MB, comfortably inside the ~16 MB
v5e VMEM even with double buffering.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e38


def dot_precision(dtype):
    """MXU precision of the kernel's dots: bf16 operands multiply exactly
    in one pass; float32 operands need the full-precision passes on TPU."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                scale: float, causal: bool, window: int, bq: int, bk: int,
                nk: int, seq_q: int, seq_k: int, precision):
    ik = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    need = jnp.bool_(True)
    if causal:
        # skip fully-masked kv blocks (upper triangle)
        need = jnp.logical_and(need, (ik * bk) <= (iq * bq + bq - 1))
    if window:
        # skip kv blocks entirely left of the sliding window
        need = jnp.logical_and(
            need, (iq * bq) - ((ik + 1) * bk - 1) < window)

    @pl.when(need)
    def _compute():
        v = v_ref[...]
        s = jax.lax.dot_general(q_ref[...], k_ref[...],
                                (((1,), (1,)), ((), ())),
                                precision=precision,
                                preferred_element_type=jnp.float32) * scale
        ok = jnp.logical_and(q_pos < seq_q, k_pos < seq_k)
        if causal:
            ok = jnp.logical_and(ok, q_pos >= k_pos)
        if window:
            ok = jnp.logical_and(ok, (q_pos - k_pos) < window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]                                  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        # rows with no valid key yet keep m == NEG_INF; zero their p
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


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0, bq: int = 128,
                    bk: int = 128, interpret: bool = False) -> jax.Array:
    """q (B,S,H,hd); k/v (B,T,K,hd).  Returns (B,S,H,hd)."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    g = H // K
    scale = hd ** -0.5

    bq = min(bq, max(S, 8))
    bk = min(bk, max(T, 8))
    Sp = math.ceil(S / bq) * bq
    Tp = math.ceil(T / bk) * bk
    nq, nk = Sp // bq, Tp // bk

    qr = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    kr = jnp.pad(k, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    vr = jnp.pad(v, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    qr = qr.transpose(0, 2, 1, 3).reshape(B * H, Sp, hd)
    kr = kr.transpose(0, 2, 1, 3).reshape(B * K, Tp, hd)
    vr = vr.transpose(0, 2, 1, 3).reshape(B * K, Tp, hd)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, nk=nk, seq_q=S, seq_k=T,
        precision=dot_precision(q.dtype))

    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((None, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((None, bk, hd), lambda bh, iq, ik, g=g: (bh // g, ik, 0)),
            pl.BlockSpec((None, bk, hd), lambda bh, iq, ik, g=g: (bh // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    out = out.reshape(B, H, Sp, hd).transpose(0, 2, 1, 3)
    return out[:, :S]
