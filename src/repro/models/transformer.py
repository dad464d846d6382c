"""Block assembly + scan-over-layer-groups decoder stack.

The per-layer pattern (cfg.block_pattern) is cycled into *groups* of one
period each; ``lax.scan`` runs over the groups with stacked parameters
(compact HLO, compile time independent of depth — essential for the
512-device dry-run).  A non-divisible tail (recurrentgemma's 26 = 3*8 + 2)
is applied unrolled.

Block kinds: "global"/"local" (attention + dense-or-MoE FFN), "rwkv"
(time-mix + channel-mix), "recurrent" (RG-LRU + MLP).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (
    ATTN_GLOBAL, ATTN_LOCAL, RECURRENT, RWKV, ModelConfig,
)
from repro.models import attention as attn
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models.common import PD, AxisRules, rms_norm

AUX_KEYS = ("moe_lb_loss", "moe_z_loss", "moe_drop_frac", "moe_load_cv")


def _zeros_aux() -> Dict[str, jax.Array]:
    return {k: jnp.float32(0.0) for k in AUX_KEYS}


# ---------------------------------------------------------------------------
# Param descriptors
# ---------------------------------------------------------------------------
def block_pds(cfg: ModelConfig, kind: str) -> Dict[str, Any]:
    d = cfg.d_model
    p: Dict[str, Any] = {
        "ln1": PD((d,), ("embed",), "zeros"),
        "ln2": PD((d,), ("embed",), "zeros"),
    }
    if cfg.post_block_norm:
        p["ln1_post"] = PD((d,), ("embed",), "zeros")
        p["ln2_post"] = PD((d,), ("embed",), "zeros")
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        p["attn"] = attn.attn_pds(cfg)
        if cfg.cross_attention:
            p["xattn"] = attn.attn_pds(cfg, cross=True)
            p["ln_x"] = PD((d,), ("embed",), "zeros")
        if cfg.moe is not None:
            p["moe"] = moe_mod.moe_pds(cfg)
            if cfg.moe.num_shared_experts:
                p["shared_mlp"] = mlp_mod.mlp_pds(
                    cfg, cfg.moe.expert_d_ff * cfg.moe.num_shared_experts)
        else:
            p["mlp"] = mlp_mod.mlp_pds(cfg)
    elif kind == RWKV:
        p["tm"] = rwkv_mod.timemix_pds(cfg)
        p["cm"] = rwkv_mod.channelmix_pds(cfg)
    elif kind == RECURRENT:
        p["rec"] = rglru_mod.rglru_pds(cfg)
        p["mlp"] = mlp_mod.mlp_pds(cfg)
    else:
        raise ValueError(kind)
    return p


def block_cache_pds(cfg: ModelConfig, kind: str, batch: int, seq: int,
                    memory_len: int = 0) -> Dict[str, Any]:
    d = cfg.d_model
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        clen = cfg.kv_cache_len(seq, kind)
        c = attn.cache_pds(cfg, batch, clen)
        if cfg.cross_attention and memory_len:
            K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
            c["xk"] = PD((batch, memory_len, K, hd), ("batch", None, None, None), "zeros")
            c["xv"] = PD((batch, memory_len, K, hd), ("batch", None, None, None), "zeros")
        return c
    if kind == RWKV:
        H, hs = d // cfg.rwkv_head_size, cfg.rwkv_head_size
        return {
            "tm_shift": PD((batch, d), ("batch", "embed"), "zeros"),
            "cm_shift": PD((batch, d), ("batch", "embed"), "zeros"),
            "state": PD((batch, H, hs, hs), ("batch", "heads", None, None),
                        "zeros", jnp.float32),
        }
    if kind == RECURRENT:
        W = cfg.conv1d_width
        return {
            "conv_tail": PD((batch, W - 1, d), ("batch", None, "mlp"), "zeros"),
            "h": PD((batch, d), ("batch", "mlp"), "zeros", jnp.float32),
        }
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------
def _ffn_train(cfg, p, h, ax, *, train: bool):
    if cfg.moe is not None:
        y, aux = moe_mod.moe_apply(cfg, p["moe"], h, ax, train=train)
        if cfg.moe.num_shared_experts:
            y = y + mlp_mod.mlp_apply(cfg, p["shared_mlp"], h, ax)
        return y, aux
    return mlp_mod.mlp_apply(cfg, p["mlp"], h, ax), _zeros_aux()


def _post(cfg, p, name, y):
    if cfg.post_block_norm:
        return rms_norm(y, p[name], cfg.rms_eps, zero_centered=True)
    return y


def block_train(cfg: ModelConfig, kind: str, p, x, ax: AxisRules, *,
                causal: bool = True, train: bool = True,
                memory: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence block forward (no cache)."""
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        window = cfg.sliding_window if kind == ATTN_LOCAL else 0
        h = rms_norm(x, p["ln1"], cfg.rms_eps, zero_centered=True)
        a = attn.attention_train(cfg, p["attn"], h, ax, window=window, causal=causal)
        x = x + _post(cfg, p, "ln1_post", a)
        if memory is not None:
            hx = rms_norm(x, p["ln_x"], cfg.rms_eps, zero_centered=True)
            x = x + attn.attention_train(cfg, p["xattn"], hx, ax, memory=memory)
        h = rms_norm(x, p["ln2"], cfg.rms_eps, zero_centered=True)
        f, aux = _ffn_train(cfg, p, h, ax, train=train)
        return x + _post(cfg, p, "ln2_post", f), aux
    if kind == RWKV:
        h = rms_norm(x, p["ln1"], cfg.rms_eps, zero_centered=True)
        B, _, d = x.shape
        H, hs = d // cfg.rwkv_head_size, cfg.rwkv_head_size
        y, _, _ = rwkv_mod.timemix_apply(
            cfg, p["tm"], h, ax,
            prev_shift=jnp.zeros((B, d), x.dtype),
            prev_state=jnp.zeros((B, H, hs, hs), jnp.float32))
        x = x + y
        h = rms_norm(x, p["ln2"], cfg.rms_eps, zero_centered=True)
        y, _ = rwkv_mod.channelmix_apply(cfg, p["cm"], h, ax,
                                         prev_shift=jnp.zeros((B, d), x.dtype))
        return x + y, _zeros_aux()
    if kind == RECURRENT:
        B, _, d = x.shape
        h = rms_norm(x, p["ln1"], cfg.rms_eps, zero_centered=True)
        y, _, _ = rglru_mod.rglru_apply(
            cfg, p["rec"], h, ax,
            conv_tail=jnp.zeros((B, cfg.conv1d_width - 1, d), x.dtype),
            h0=jnp.zeros((B, d), jnp.float32))
        x = x + y
        h = rms_norm(x, p["ln2"], cfg.rms_eps, zero_centered=True)
        return x + mlp_mod.mlp_apply(cfg, p["mlp"], h, ax), _zeros_aux()
    raise ValueError(kind)


def block_prefill(cfg: ModelConfig, kind: str, p, x, ax: AxisRules, *,
                  memory: Optional[jax.Array] = None, cache_len: int = 0,
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Forward that also produces the decode cache entry for this block."""
    B, S, d = x.shape
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        window = cfg.sliding_window if kind == ATTN_LOCAL else 0
        h = rms_norm(x, p["ln1"], cfg.rms_eps, zero_centered=True)
        # recompute k/v for the cache (cheap relative to attention)
        pos = jnp.broadcast_to(jnp.arange(S), (B, S))
        _, k, v = attn._project_qkv(cfg, p["attn"], h, pos, ax)
        a = attn.attention_train(cfg, p["attn"], h, ax, window=window, causal=True)
        x = x + _post(cfg, p, "ln1_post", a)
        cache = _kv_to_cache(cfg, k, v, cache_len or S, window, ax)
        if memory is not None:
            hx = rms_norm(x, p["ln_x"], cfg.rms_eps, zero_centered=True)
            x = x + attn.attention_train(cfg, p["xattn"], hx, ax, memory=memory)
            cache["xk"] = jnp.einsum("bsd,dhk->bshk", memory, p["xattn"]["wk"])
            cache["xv"] = jnp.einsum("bsd,dhk->bshk", memory, p["xattn"]["wv"])
        h = rms_norm(x, p["ln2"], cfg.rms_eps, zero_centered=True)
        f, _ = _ffn_train(cfg, p, h, ax, train=False)
        return x + _post(cfg, p, "ln2_post", f), cache
    if kind == RWKV:
        h = rms_norm(x, p["ln1"], cfg.rms_eps, zero_centered=True)
        H, hs = d // cfg.rwkv_head_size, cfg.rwkv_head_size
        y, tm_shift, state = rwkv_mod.timemix_apply(
            cfg, p["tm"], h, ax,
            prev_shift=jnp.zeros((B, d), x.dtype),
            prev_state=jnp.zeros((B, H, hs, hs), jnp.float32))
        x = x + y
        h = rms_norm(x, p["ln2"], cfg.rms_eps, zero_centered=True)
        y, cm_shift = rwkv_mod.channelmix_apply(
            cfg, p["cm"], h, ax, prev_shift=jnp.zeros((B, d), x.dtype))
        return x + y, {"tm_shift": tm_shift, "cm_shift": cm_shift, "state": state}
    if kind == RECURRENT:
        h = rms_norm(x, p["ln1"], cfg.rms_eps, zero_centered=True)
        y, tail, hlast = rglru_mod.rglru_apply(
            cfg, p["rec"], h, ax,
            conv_tail=jnp.zeros((B, cfg.conv1d_width - 1, d), x.dtype),
            h0=jnp.zeros((B, d), jnp.float32))
        x = x + y
        h = rms_norm(x, p["ln2"], cfg.rms_eps, zero_centered=True)
        return x + mlp_mod.mlp_apply(cfg, p["mlp"], h, ax), \
            {"conv_tail": tail, "h": hlast}
    raise ValueError(kind)


def _kv_to_cache(cfg, k, v, cache_len, window, ax: AxisRules):
    """Store prefill K/V into a (possibly ring) cache of length cache_len."""
    S = k.shape[1]
    eff = min(window, cache_len) if window else cache_len
    if S >= eff:
        ck, cv = k[:, S - eff:], v[:, S - eff:]
        if window and eff == cache_len:
            # ring semantics: absolute position p lives at slot p % cache_len
            # (decode writes at pos % cache_len), so rotate the stored window.
            ck = jnp.roll(ck, S % cache_len, axis=1)
            cv = jnp.roll(cv, S % cache_len, axis=1)
        if eff < cache_len:
            pad = [(0, 0), (0, cache_len - eff), (0, 0), (0, 0)]
            ck, cv = jnp.pad(ck, pad), jnp.pad(cv, pad)
    else:
        pad = [(0, 0), (0, cache_len - S), (0, 0), (0, 0)]
        ck, cv = jnp.pad(k, pad), jnp.pad(v, pad)
    ck = ax.constrain(ck, "batch", "kv_seq", None, None)
    cv = ax.constrain(cv, "batch", "kv_seq", None, None)
    return {"k": ck, "v": cv}


def block_decode(cfg: ModelConfig, kind: str, p, x, cache, pos, ax: AxisRules,
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Single-token step.  x (B,1,D); pos (B,) int32.  Returns the output
    and what changed of the block's cache, for ``write_decode``: the new
    K/V rows of an attention block, the whole new state of a recurrent one.
    """
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        window = cfg.sliding_window if kind == ATTN_LOCAL else 0
        h = rms_norm(x, p["ln1"], cfg.rms_eps, zero_centered=True)
        a, rows = attn.attention_decode(cfg, p["attn"], h, cache, pos, ax,
                                        window=window)
        x = x + _post(cfg, p, "ln1_post", a)
        if cfg.cross_attention and "xk" in cache:
            hx = rms_norm(x, p["ln_x"], cfg.rms_eps, zero_centered=True)
            a, _ = attn.attention_decode(cfg, p["xattn"], hx, {}, pos, ax,
                                         memory_kv=(cache["xk"], cache["xv"]))
            x = x + a
        h = rms_norm(x, p["ln2"], cfg.rms_eps, zero_centered=True)
        f, _ = _ffn_train(cfg, p, h, ax, train=False)
        return x + _post(cfg, p, "ln2_post", f), rows
    if kind == RWKV:
        h = rms_norm(x, p["ln1"], cfg.rms_eps, zero_centered=True)
        y, tm_shift, state = rwkv_mod.timemix_decode(
            cfg, p["tm"], h, ax, prev_shift=cache["tm_shift"],
            prev_state=cache["state"])
        x = x + y
        h = rms_norm(x, p["ln2"], cfg.rms_eps, zero_centered=True)
        y, cm_shift = rwkv_mod.channelmix_apply(
            cfg, p["cm"], h, ax, prev_shift=cache["cm_shift"])
        x = x + y
        return x, {"tm_shift": tm_shift, "cm_shift": cm_shift, "state": state}
    if kind == RECURRENT:
        h = rms_norm(x, p["ln1"], cfg.rms_eps, zero_centered=True)
        y, tail, hlast = rglru_mod.rglru_decode(
            cfg, p["rec"], h, ax, conv_tail=cache["conv_tail"], h0=cache["h"])
        x = x + y
        h = rms_norm(x, p["ln2"], cfg.rms_eps, zero_centered=True)
        x = x + mlp_mod.mlp_apply(cfg, p["mlp"], h, ax)
        return x, {"conv_tail": tail, "h": hlast}
    raise ValueError(kind)


def write_decode(kind: str, cache, new, pos) -> Dict[str, jax.Array]:
    """A block's cache after a decode step, from what ``block_decode``
    returned.  Attention K/V rows (B,K,hd) are written at slot ``pos % Sc``
    of each batch row and nothing else of the cache moves, so a donated
    cache is updated in place; leaves with no update (cross-attention
    memory) are kept; recurrent states are small and replaced whole.
    Leaves may carry a leading layer axis (the scanned groups), as may
    ``new``."""
    if kind not in (ATTN_GLOBAL, ATTN_LOCAL):
        return {**cache, **new}
    out = dict(cache)
    for name in ("k", "v"):
        c = cache[name]
        # every index, the layer's too, is a scatter index, so each update
        # is one (K, hd) row, contiguous in the cache's layout; a slice over
        # the layer axis has XLA relayout the whole cache around the scatter
        idx = (jnp.arange(c.shape[-4]), pos % c.shape[-3])
        if c.ndim == 5:
            idx = (jnp.arange(c.shape[0])[:, None],) + tuple(
                i[None] for i in idx)
        out[name] = c.at[idx].set(new[name].astype(c.dtype),
                                  unique_indices=True)
    return out
