"""GQA attention: training/prefill (full-sequence) and decode (KV cache).

Variants covered (per assigned archs): GQA with any (H, K), qk_norm (qwen3),
attention-logit softcap (gemma2), sliding-window/local attention (gemma2,
mixtral, recurrentgemma), cross-attention (seamless enc-dec), bidirectional
encoders.

TP mapping (megatron-style over the "model" mesh axis, see DESIGN.md):
- train/prefill: KV heads are *replicated* ``rep = tp/gcd(K, tp)`` times —
  exactly what real TP serving engines do when ``kv_heads < tp`` — so the
  q-head axis shards evenly.  If H itself is not divisible by tp
  (recurrentgemma's 10 heads), attention runs replicated on the model axis.
- decode: the KV cache shards over (batch -> data, seq -> model); the
  per-step softmax over the sequence-sharded axis costs two tiny
  all-reduces (flash-decode-style TP).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import (
    PD, AxisRules, apply_rope, rms_norm, rope_freqs, softcap,
)

NEG_INF = -2.0e38


def attn_pds(cfg: ModelConfig, cross: bool = False) -> Dict[str, PD]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, K = cfg.num_heads, cfg.num_kv_heads
    p = {
        "wq": PD((d, H, hd), ("embed", "heads", None)),
        "wk": PD((d, K, hd), ("embed", "kv", None)),
        "wv": PD((d, K, hd), ("embed", "kv", None)),
        "wo": PD((H, hd, d), ("heads", None, "embed")),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = PD((hd,), (None,), "zeros")
        p["k_norm"] = PD((hd,), (None,), "zeros")
    return p


def kv_replication(cfg: ModelConfig, ax: AxisRules) -> int:
    """How many times KV heads are replicated for TP train/prefill."""
    tp = ax.model_size()
    H, K = cfg.num_heads, cfg.num_kv_heads
    if tp <= 1 or H % tp != 0:
        return 1
    rep = tp // math.gcd(K, tp)
    return rep if (H // K) % rep == 0 else 1


def _project_qkv(cfg: ModelConfig, p, x, positions, ax: AxisRules,
                 rope: bool = True):
    """x (B,S,D) -> q (B,S,H,hd), k/v (B,S,K,hd) with qk_norm + RoPE."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps, zero_centered=True)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps, zero_centered=True)
    if rope:
        cos, sin = rope_freqs(positions, cfg.resolved_head_dim, cfg.rope_theta)
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = ax.constrain(q, "batch", None, "heads", None)
    return q, k, v


def _mask_bias(q_pos, k_pos, *, causal: bool, window: int) -> jax.Array:
    """(S, T) additive bias in f32: 0 where attendable, -inf elsewhere."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok &= q_pos[:, None] >= k_pos[None, :]
    if window:
        ok &= (q_pos[:, None] - k_pos[None, :]) < window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _sdpa(cfg: ModelConfig, q, k, v, bias, ax: AxisRules) -> jax.Array:
    """Grouped-head attention.  q (B,S,Kr,G,hd); k,v (B,T,Kr,hd)."""
    scale = cfg.resolved_head_dim ** -0.5
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32) * scale
    if cfg.attn_logit_softcap:
        scores = softcap(scores, cfg.attn_logit_softcap)
    scores = scores + bias[None, None, None, :, :]
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out


def _sdpa_blockwise(cfg: ModelConfig, q, k, v, q_pos, k_pos, ax: AxisRules,
                    *, causal: bool, window: int, block: int = 1024
                    ) -> jax.Array:
    """Flash-style online-softmax attention in pure XLA (lax.scan over KV
    blocks).  Never materializes the (S, T) score matrix to HBM: per step
    only a (B,Kr,G,S,block) tile lives inside the (rematerialized) scan
    body, so both the memory-roofline term and peak temp drop by ~T/block.
    The backward pass recomputes block scores (jax.checkpoint on the body),
    exactly like FlashAttention's backward — this is the XLA-lowerable
    twin of kernels/flash_attention.py for the 512-device dry-run.
    """
    B, S, Kr, G, hd = q.shape
    T = k.shape[1]
    scale = hd ** -0.5
    nb = (T + block - 1) // block
    Tp = nb * block
    if Tp != T:
        k = jnp.pad(k, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, Tp - T), constant_values=2**30)
    kb = k.reshape(B, nb, block, Kr, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nb, block, Kr, hd).transpose(1, 0, 2, 3, 4)
    pb = k_pos.reshape(nb, block)
    qf = q.astype(jnp.float32)

    def body(carry, inp):
        m, l, acc = carry
        kblk, vblk, pblk = inp
        s = jnp.einsum("bskgd,btkd->bkgst", qf,
                       kblk.astype(jnp.float32)) * scale
        if cfg.attn_logit_softcap:
            s = softcap(s, cfg.attn_logit_softcap)
        ok = jnp.ones((S, block), bool)
        if causal:
            ok &= q_pos[:, None] >= pblk[None, :]
        if window:
            ok &= (q_pos[:, None] - pblk[None, :]) < window
        ok &= pblk[None, :] < 2**30
        s = jnp.where(ok[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where((m_new == NEG_INF)[..., None], 0.0, p)
        alpha = jnp.where(m == NEG_INF, 0.0, jnp.exp(m - m_new))
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgst,btkd->bkgsd", p, vblk.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((B, Kr, G, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Kr, G, S), jnp.float32)
    a0 = jnp.zeros((B, Kr, G, S, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable),
        (m0, l0, a0), (kb, vb, pb))
    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)  # (B,S,Kr,G,hd)


def attention_train(cfg: ModelConfig, p, x, ax: AxisRules, *,
                    window: int = 0, causal: bool = True,
                    positions: Optional[jax.Array] = None,
                    memory: Optional[jax.Array] = None,
                    memory_positions: Optional[jax.Array] = None) -> jax.Array:
    """Full-sequence attention.  memory != None => cross-attention."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    if memory is None:
        q, k, v = _project_qkv(cfg, p, x, positions, ax)
        k_pos = positions
    else:
        # cross-attention: q from x, k/v from encoder memory; no RoPE on q/k
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        k = jnp.einsum("bsd,dhk->bshk", memory, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", memory, p["wv"])
        q = ax.constrain(q, "batch", None, "heads", None)
        k_pos = (memory_positions if memory_positions is not None
                 else jnp.broadcast_to(jnp.arange(memory.shape[1]), (B, memory.shape[1])))
        causal, window = False, 0

    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    rep = kv_replication(cfg, ax)
    if rep > 1:  # replicate KV heads across TP ranks
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    Kr = K * rep
    k = ax.constrain(k, "batch", None, "heads" if Kr % max(ax.model_size(), 1) == 0 else None, None)
    v = ax.constrain(v, "batch", None, "heads" if Kr % max(ax.model_size(), 1) == 0 else None, None)
    q = q.reshape(B, S, Kr, H // Kr, hd)

    if ax.opt("attn_impl", "naive") == "blockwise":
        out = _sdpa_blockwise(cfg, q, k, v, positions[0], k_pos[0], ax,
                              causal=causal, window=window,
                              block=int(ax.opt("attn_block", 1024)))
    else:
        bias = _mask_bias(positions[0], k_pos[0], causal=causal, window=window)
        out = _sdpa(cfg, q, k, v, bias, ax)
    out = out.reshape(B, S, H, hd)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return ax.constrain(y, "batch", None, "embed")


# ---------------------------------------------------------------------------
# Decode path (single new token against a KV cache)
# ---------------------------------------------------------------------------
def cache_pds(cfg: ModelConfig, batch: int, cache_len: int) -> Dict[str, PD]:
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": PD((batch, cache_len, K, hd), ("batch", "kv_seq", None, None), "zeros"),
        "v": PD((batch, cache_len, K, hd), ("batch", "kv_seq", None, None), "zeros"),
    }


def attention_decode(cfg: ModelConfig, p, x, cache: Dict[str, jax.Array],
                     pos: jax.Array, ax: AxisRules, *, window: int = 0,
                     memory_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode.  x (B,1,D); cache k/v (B,Sc,K,hd); pos (B,) int.

    Reads the cache as it stands and returns the output with the new
    token's K/V rows ``{"k", "v"}`` (B,K,hd), which the caller writes at
    slot ``pos % Sc`` (``transformer.write_decode``): the softmax runs over
    the cache's valid entries and the new row's own score together, so the
    cache is never rewritten here.  Sliding-window caches are ring buffers
    of length ``min(window, S)``; entries carry RoPE at their absolute
    positions so no re-rotation is needed.  Cross-attention (enc-dec)
    passes precomputed ``memory_kv`` and returns no rows.
    """
    B = x.shape[0]
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    scale = hd ** -0.5

    def scores(q, k):
        # q (B,H,hd); k (B,T,K,hd) -> (B,K,G,T), f32
        qg = q.reshape(B, K, H // K, hd)
        s = jnp.einsum("bkgd,btkd->bkgt", qg, k).astype(jnp.float32) * scale
        if cfg.attn_logit_softcap:
            s = softcap(s, cfg.attn_logit_softcap)
        return s

    def mix(pr, v):
        # pr (B,K,G,T) f32; v (B,T,K,hd) -> (B,K,G,hd), f32
        return jnp.einsum("bkgt,btkd->bkgd", pr.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)

    def out(o):
        y = jnp.einsum("bhk,hkd->bd", o.reshape(B, H, hd).astype(x.dtype),
                       p["wo"])[:, None, :]
        return ax.constrain(y, "batch", None, "embed")

    if memory_kv is not None:  # cross-attention: static memory KV
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])[:, 0]
        ck, cv = memory_kv
        return out(mix(jax.nn.softmax(scores(q, ck), axis=-1), cv)), {}

    ck = ax.constrain(cache["k"], "batch", "kv_seq", None, None)
    cv = ax.constrain(cache["v"], "batch", "kv_seq", None, None)
    q, k_new, v_new = _project_qkv(cfg, p, x, pos[:, None], ax)
    # the new row as the cache will hold it
    k_new, v_new = k_new.astype(ck.dtype), v_new.astype(cv.dtype)
    Sc = ck.shape[1]
    t = jnp.arange(Sc)[None, :]
    pos = pos[:, None]
    # old entries that stay attendable: a ring buffer is full once
    # pos + 1 >= Sc, else only t < pos holds real entries; slot pos % Sc is
    # the one the new row replaces (position pos - Sc, out of the window)
    valid = ((t <= pos) | (pos + 1 >= Sc)) & (t != pos % Sc)
    s = jnp.where(valid[:, None, None, :], scores(q[:, 0], ck), NEG_INF)
    s1 = scores(q[:, 0], k_new)                         # (B,K,G,1)
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), s1)
    e, e1 = jnp.exp(s - m), jnp.exp(s1 - m)
    l = jnp.sum(e, axis=-1, keepdims=True) + e1
    o = mix(e / l, cv) + mix(e1 / l, v_new)
    return out(o), {"k": k_new[:, 0], "v": v_new[:, 0]}
