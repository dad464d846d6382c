"""Plain reference of a Llama-style decoder, written from the published
description and importing nothing of the program: token embedding, then per
layer RMSNorm, grouped-query attention with rotary positions (rotate-half,
theta from the configuration), a residual, RMSNorm and a SwiGLU FFN, dense
(Qwen2) or a sparse mixture of experts (Mixtral: softmax router over every
expert, the top k kept and renormalised, each token through its k experts);
a final RMSNorm and an untied output head.

It runs the full causal forward over each sequence, in float32 with
matrix products at ``highest`` precision, one layer at a time: a layer's
weights are drawn again from the seed (``bench/weights.py``) and dropped
before the next.  Every sequence is padded at the end to the engine's
``max_seq``, so each program has one shape and compiles once; causal
attention keeps the padding out of every real position.

Departure from the published Qwen2: its q, k and v projections carry a
bias; the program's have none, so the benchmark's weights hold none and the
reference adds none (a zero bias).

``quant="fp8"`` is the control: both operands of every projection (not the
router) are rounded to float8_e4m3fn, activations per token and weights per
output channel, each scaled to the format's range.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.shapes import Dims

F8_MAX = 448.0


def _fq(v, axes):
    """Round to float8_e4m3fn with one scale per slice over ``axes``."""
    s = jnp.max(jnp.abs(v), axis=axes, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (v / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _proj(spec, x, w, quant, w_axes):
    if quant:
        x = _fq(x, (-1,))
        w = _fq(w, w_axes)
    return jnp.einsum(spec, x, w)


def _rms(x, delta, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * (1.0 + delta)


def _rope(x, theta):
    """x (B, T, heads, hd), rotate-half at positions 0..T-1."""
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("m", "eps", "theta", "quant"))
def _attention_block(x, w, *, m: Dims, eps, theta, quant):
    """x + attention(RMSNorm(x)); x (B, T, d) float32."""
    B, T, _ = x.shape
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    h = _rms(x, w["attn_norm"], eps)
    q = _rope(_proj("btd,dhk->bthk", h, w["wq"], quant, (0,)), theta)
    k = _rope(_proj("btd,dhk->bthk", h, w["wk"], quant, (0,)), theta)
    v = _proj("btd,dhk->bthk", h, w["wv"], quant, (0,))
    G = m.heads // m.kv_heads
    q = q.reshape(B, T, m.kv_heads, G, m.head_dim)
    s = jnp.einsum("btkgd,bskd->bkgts", q, k) * m.head_dim ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bkgts,bskd->btkgd", jax.nn.softmax(s, -1), v)
    o = o.reshape(B, T, m.heads, m.head_dim)
    return x + _proj("bthk,hkd->btd", o, w["wo"], quant, (0, 1))


def _swiglu(h, wg, wu, wd, quant):
    g = _proj("nd,df->nf", h, wg, quant, (0,))
    u = _proj("nd,df->nf", h, wu, quant, (0,))
    return _proj("nf,fd->nd", jax.nn.silu(g) * u, wd, quant, (0,))


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _dense_block(x, w, *, eps, quant):
    """x + SwiGLU(RMSNorm(x)); x (N, d) float32."""
    h = _rms(x, w["mlp_norm"].astype(jnp.float32), eps)
    f = lambda k: w[k].astype(jnp.float32)  # noqa: E731
    return x + _swiglu(h, f("w_gate"), f("w_up"), f("w_down"), quant)


@functools.partial(jax.jit, static_argnames=("m", "eps", "quant"))
def _moe_block(x, w, *, m: Dims, eps, quant):
    """x + sparse MoE(RMSNorm(x)); x (N, d) float32.  Each expert runs on
    every token and is weighted by the token's gate for it, 0 unless the
    expert is among its top k, so a token's output sums its k experts."""
    f = lambda a: a.astype(jnp.float32)  # noqa: E731
    h = _rms(x, f(w["mlp_norm"]), eps)
    probs = jax.nn.softmax(h @ f(w["router"]), -1)
    top, ids = jax.lax.top_k(probs, m.top_k)
    gates = top / jnp.sum(top, -1, keepdims=True)
    dense = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], ids].set(gates)

    def expert(y, e):
        out = _swiglu(h, f(w["w_gate"][e]), f(w["w_up"][e]),
                      f(w["w_down"][e]), quant)
        return y + dense[:, e][:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(m.experts))
    return x + y


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _logits(rows, norm, head, *, eps, quant):
    h = _rms(rows, norm.astype(jnp.float32), eps)
    return _proj("nd,dv->nv", h, head.astype(jnp.float32), quant, (0,))


def logits(conf, m: Dims, seed: int, seqs: Sequence[np.ndarray],
           rows: Sequence[np.ndarray], quant: Optional[str] = None):
    """For each sequence, float32 logits at its positions ``rows``."""
    eps, theta = float(conf["rms_norm_eps"]), float(conf["rope_theta"])
    T = int(conf["serving"]["max_seq"])   # one shape: every program caches
    q = quant == "fp8"
    s32 = weights.seed32(seed)
    bf16 = jnp.bfloat16
    top = weights.top_fn(m, bf16)(s32)
    xs = []
    with jax.default_matmul_precision("highest"):
        for seq in seqs:
            toks = np.zeros((1, T), np.int32)
            toks[0, :len(seq)] = seq
            xs.append(top["embed"][toks].astype(jnp.float32))
        for layer in range(m.layers):
            w = weights.layer_fn(m, bf16)(s32, layer)
            attn = {k: w[k] for k in ("attn_norm", "wq", "wk", "wv", "wo")}
            ffn = {k: w[k] for k in ("mlp_norm", "w_gate", "w_up", "w_down",
                                     "router") if k in w}
            for b, x in enumerate(xs):
                x = _attention_block(x, attn, m=m, eps=eps, theta=theta,
                                     quant=q)[0]
                if m.experts:
                    x = _moe_block(x, ffn, m=m, eps=eps, quant=q)
                else:
                    x = _dense_block(x, ffn, eps=eps, quant=q)
                xs[b] = x[None]
            del w, attn, ffn
        return [np.asarray(_logits(x[0][np.asarray(r)], top["final_norm"],
                                   top["head"], eps=eps, quant=q))
                for x, r in zip(xs, rows)]
