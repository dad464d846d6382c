"""Model assembly: decoder-only LM and encoder-decoder, with scan-over-groups.

Public API (used by launch/, serving/, training/, tests/):

    model = build_model(cfg, ax, remat="none")
    pds    = model.pds()                  # param descriptors
    params = common.init_tree(key, pds, dtype)
    loss   = model.loss(params, batch)
    logits, cache = model.prefill(params, batch)   # last=(B,) picks rows
    logits, cache = model.decode(params, cache, tokens, pos)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import (
    ATTN_GLOBAL, ModelConfig, ShapeConfig,
)
from repro.models import transformer as tfm
from repro.models.common import (
    PD, AxisRules, cross_entropy_loss, rms_norm, softcap, stack_pds,
)
from repro.models.transformer import AUX_KEYS


def _logit_rows(x: jax.Array, all_logits: bool,
                last: Optional[jax.Array]) -> jax.Array:
    """The prefill positions whose logits are returned: every position, the
    final one, or per row the position ``last`` (a padded prompt's end)."""
    if all_logits:
        return x
    if last is None:
        return x[:, -1:, :]
    return jnp.take_along_axis(x, last[:, None, None], axis=1)


def _tree_sum(trees):
    out = {k: jnp.float32(0.0) for k in AUX_KEYS}
    for t in trees:
        for k in AUX_KEYS:
            v = t[k]
            out[k] = out[k] + (jnp.sum(v) if getattr(v, "ndim", 0) else v)
    return out


class LM:
    """Decoder-only LM covering dense / moe / ssm / hybrid / vlm families."""

    def __init__(self, cfg: ModelConfig, ax: AxisRules, *, remat: str = "none"):
        self.cfg = cfg
        self.ax = ax
        self.remat = remat
        pat = cfg.pattern
        period = len(cfg.block_pattern)
        self.n_groups = cfg.num_layers // period
        self.period_kinds = tuple(pat[:period])
        self.tail_kinds = tuple(pat[self.n_groups * period:])

    # ------------------------------------------------------------ params --
    def pds(self) -> Dict[str, Any]:
        cfg = self.cfg
        tree: Dict[str, Any] = {
            "embed": PD((cfg.padded_vocab, cfg.d_model), ("vocab", "embed"), 0.02),
            "final_norm": PD((cfg.d_model,), ("embed",), "zeros"),
            "groups": tuple(
                stack_pds(tfm.block_pds(cfg, kind), self.n_groups)
                for kind in self.period_kinds),
            "tail": tuple(tfm.block_pds(cfg, kind) for kind in self.tail_kinds),
        }
        if not cfg.tie_embeddings:
            tree["head"] = PD((cfg.d_model, cfg.padded_vocab), ("embed", "vocab"), 0.02)
        return tree

    # --------------------------------------------------------- embeddings --
    def _embed(self, params, ids: jax.Array) -> jax.Array:
        """Megatron-style vocab-sharded lookup (local gather + psum)."""
        cfg, ax = self.cfg, self.ax
        emb = params["embed"]
        tp = ax.model_size()
        if ax.mesh is None or tp <= 1 or cfg.padded_vocab % tp != 0:
            x = emb[ids]
        else:
            Vl = cfg.padded_vocab // tp
            bspec = ax.batch(ids.shape[0])

            def body(e_l, ids_l):
                j = jax.lax.axis_index("model")
                loc = ids_l - j * Vl
                ok = (loc >= 0) & (loc < Vl)
                g = e_l[jnp.clip(loc, 0, Vl - 1)]
                g = jnp.where(ok[..., None], g, 0)
                return jax.lax.psum(g, "model")

            x = jax.shard_map(
                body, mesh=ax.mesh,
                in_specs=(P("model", None), P(bspec, None)),
                out_specs=P(bspec, None, None), check_vma=False,
            )(emb, ids)
        if cfg.tie_embeddings:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        return self.ax.constrain(x, "batch", None, "embed")

    def _inputs_to_x(self, params, batch) -> jax.Array:
        cfg = self.cfg
        x = self._embed(params, batch["tokens"])
        if cfg.frontend == "patch" and "embeds" in batch:
            pe = batch["embeds"].astype(x.dtype)
            pe = self.ax.constrain(pe, "batch", None, "embed")
            x = jnp.concatenate([pe, x], axis=1)
        return x

    def _logits(self, params, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        x = rms_norm(x, params["final_norm"], cfg.rms_eps, zero_centered=True)
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
        else:
            logits = jnp.einsum("bsd,dv->bsv", x, params["head"])
        logits = softcap(logits, cfg.final_logit_softcap)
        return self.ax.constrain(logits, "batch", None, "vocab")

    # ------------------------------------------------------------- stacks --
    def _scan_train(self, params, x, *, causal=True, train=True, memory=None):
        cfg, ax = self.cfg, self.ax
        kinds = self.period_kinds

        def group_fn(x, gp):
            auxes = []
            for s, kind in enumerate(kinds):
                x, aux = tfm.block_train(cfg, kind, gp[s], x, ax,
                                         causal=causal, train=train,
                                         memory=memory)
                auxes.append(aux)
            return x, _tree_sum(auxes)

        fn = group_fn
        if self.remat != "none" and train:
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if self.remat == "dots" else None)
            fn = jax.checkpoint(group_fn, policy=policy)
        x, auxs = jax.lax.scan(fn, x, params["groups"])
        tails = []
        for kind, tp_ in zip(self.tail_kinds, params["tail"]):
            x, aux = tfm.block_train(cfg, kind, tp_, x, ax, causal=causal,
                                     train=train, memory=memory)
            tails.append(aux)
        aux = _tree_sum([jax.tree_util.tree_map(jnp.sum, auxs)] + tails)
        n = max(cfg.num_layers, 1)
        aux = {k: v / n for k, v in aux.items()}
        return x, aux

    def _scan_prefill(self, params, x, *, cache_len: int, memory=None):
        cfg, ax = self.cfg, self.ax
        kinds = self.period_kinds

        def group_fn(x, gp):
            caches = []
            for s, kind in enumerate(kinds):
                x, c = tfm.block_prefill(cfg, kind, gp[s], x, ax,
                                         memory=memory,
                                         cache_len=cfg.kv_cache_len(cache_len, kind))
                caches.append(c)
            return x, tuple(caches)

        x, gcaches = jax.lax.scan(group_fn, x, params["groups"])
        tcaches = []
        for kind, tp_ in zip(self.tail_kinds, params["tail"]):
            x, c = tfm.block_prefill(cfg, kind, tp_, x, ax, memory=memory,
                                     cache_len=cfg.kv_cache_len(cache_len, kind))
            tcaches.append(c)
        return x, {"groups": gcaches, "tail": tuple(tcaches)}

    def _scan_decode(self, params, cache, x, pos):
        """One token through the stack.  The scan reads each layer's cache
        and hands back what changed (new K/V rows, recurrent states); they
        are written after it (``write_decode``), so the stacked cache is
        never carried or copied.  A scalar ``pos`` is every row's."""
        cfg, ax = self.cfg, self.ax
        kinds = self.period_kinds
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (x.shape[0],))

        def group_fn(x, scanned):
            gp, gc = scanned
            new = []
            for s, kind in enumerate(kinds):
                x, n = tfm.block_decode(cfg, kind, gp[s], x, gc[s], pos, ax)
                new.append(n)
            return x, tuple(new)

        x, gnew = jax.lax.scan(group_fn, x, (params["groups"], cache["groups"]))
        groups = tuple(tfm.write_decode(kind, c, n, pos)
                       for kind, c, n in zip(kinds, cache["groups"], gnew))
        tail = []
        for kind, tp_, tc in zip(self.tail_kinds, params["tail"], cache["tail"]):
            x, n = tfm.block_decode(cfg, kind, tp_, x, tc, pos, ax)
            tail.append(tfm.write_decode(kind, tc, n, pos))
        return x, {"groups": groups, "tail": tuple(tail)}

    # -------------------------------------------------------------- steps --
    def loss(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        x = self._inputs_to_x(params, batch)
        x, aux = self._scan_train(params, x, train=True)
        logits = self._logits(params, x)
        labels = batch["labels"]
        if logits.shape[1] != labels.shape[1]:  # vlm: loss on text tail only
            logits = logits[:, logits.shape[1] - labels.shape[1]:]
        mask = labels >= 0
        loss = self._sharded_ce(logits, jnp.maximum(labels, 0), mask)
        moe_loss = 0.01 * aux["moe_lb_loss"] + 1e-3 * aux["moe_z_loss"]
        metrics = dict(aux)
        metrics["ce_loss"] = loss
        return loss + moe_loss, metrics

    def _sharded_ce(self, logits, labels, mask) -> jax.Array:
        """CE over a vocab-sharded logits tensor without big gathers."""
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape, len(lf.shape) - 1)
        picked = jnp.sum(jnp.where(iota == labels[..., None], lf, 0.0), axis=-1)
        nll = lse - picked
        m = mask.astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)

    def prefill(self, params, batch, *, cache_len: Optional[int] = None,
                all_logits: bool = False, last: Optional[jax.Array] = None):
        x = self._inputs_to_x(params, batch)
        S_total = x.shape[1]
        x, cache = self._scan_prefill(params, x, cache_len=cache_len or S_total)
        logits = self._logits(params, _logit_rows(x, all_logits, last))
        return logits, cache

    def decode(self, params, cache, tokens, pos):
        x = self._embed(params, tokens)
        x, cache = self._scan_decode(params, cache, x, pos)
        logits = self._logits(params, x)
        return logits, cache

    # ------------------------------------------------------------- shapes --
    def cache_pds(self, batch: int, seq: int, memory_len: int = 0):
        cfg = self.cfg
        g = tuple(
            stack_pds(tfm.block_cache_pds(cfg, kind, batch, seq, memory_len),
                      self.n_groups)
            for kind in self.period_kinds)
        t = tuple(tfm.block_cache_pds(cfg, kind, batch, seq, memory_len)
                  for kind in self.tail_kinds)
        return {"groups": g, "tail": t}

    def input_specs(self, shape: ShapeConfig) -> Dict[str, jax.ShapeDtypeStruct]:
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        if shape.kind == "train":
            if cfg.frontend == "patch":
                Sp = int(S * cfg.frontend_fraction)
                return {
                    "tokens": jax.ShapeDtypeStruct((B, S - Sp), i32),
                    "embeds": jax.ShapeDtypeStruct((B, Sp, cfg.d_model), jnp.bfloat16),
                    "labels": jax.ShapeDtypeStruct((B, S), i32),
                }
            return {"tokens": jax.ShapeDtypeStruct((B, S), i32),
                    "labels": jax.ShapeDtypeStruct((B, S), i32)}
        if shape.kind == "prefill":
            if cfg.frontend == "patch":
                Sp = int(S * cfg.frontend_fraction)
                return {
                    "tokens": jax.ShapeDtypeStruct((B, S - Sp), i32),
                    "embeds": jax.ShapeDtypeStruct((B, Sp, cfg.d_model), jnp.bfloat16),
                }
            return {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
        # decode
        return {"tokens": jax.ShapeDtypeStruct((B, 1), i32)}


class EncDec:
    """Encoder-decoder (seamless).  Same step API as LM."""

    def __init__(self, cfg: ModelConfig, ax: AxisRules, *, remat: str = "none"):
        self.cfg = cfg
        self.ax = ax
        enc_cfg = dataclasses.replace(cfg, cross_attention=False,
                                      num_layers=cfg.encoder_layers)
        self.encoder = LM(enc_cfg, ax, remat=remat)
        self.decoder = LM(cfg, ax, remat=remat)

    def pds(self):
        enc = self.encoder.pds()
        enc.pop("embed"), enc.pop("final_norm")
        enc.pop("head", None)
        dec = self.decoder.pds()
        d = self.cfg.d_model
        return {
            "enc": {"groups": enc["groups"], "tail": enc["tail"],
                    "norm": PD((d,), ("embed",), "zeros")},
            "dec": dec,
        }

    def encode(self, params, frames: jax.Array) -> jax.Array:
        dt = jax.tree_util.tree_leaves(params["dec"])[0].dtype
        x = self.ax.constrain(frames.astype(dt), "batch", None, "embed")
        ep = {"groups": params["enc"]["groups"], "tail": params["enc"]["tail"]}
        x, _ = self.encoder._scan_train(ep, x, causal=False, train=False)
        return rms_norm(x, params["enc"]["norm"], self.cfg.rms_eps,
                        zero_centered=True)

    def loss(self, params, batch):
        memory = self.encode(params, batch["frames"])
        x = self.decoder._embed(params["dec"], batch["tokens"])
        x, aux = self.decoder._scan_train(params["dec"], x, train=True,
                                          memory=memory)
        logits = self.decoder._logits(params["dec"], x)
        mask = batch["labels"] >= 0
        loss = self.decoder._sharded_ce(logits, jnp.maximum(batch["labels"], 0), mask)
        metrics = dict(aux)
        metrics["ce_loss"] = loss
        return loss, metrics

    def prefill(self, params, batch, *, cache_len: Optional[int] = None,
                all_logits: bool = False, last: Optional[jax.Array] = None):
        memory = self.encode(params, batch["frames"])
        x = self.decoder._embed(params["dec"], batch["tokens"])
        S = x.shape[1]
        x, cache = self.decoder._scan_prefill(params["dec"], x,
                                              cache_len=cache_len or S,
                                              memory=memory)
        logits = self.decoder._logits(params["dec"],
                                      _logit_rows(x, all_logits, last))
        return logits, cache

    def decode(self, params, cache, tokens, pos):
        x = self.decoder._embed(params["dec"], tokens)
        x, cache = self.decoder._scan_decode(params["dec"], cache, x, pos)
        logits = self.decoder._logits(params["dec"], x)
        return logits, cache

    def cache_pds(self, batch: int, seq: int, memory_len: int = 0):
        return self.decoder.cache_pds(batch, seq, memory_len or 4096)

    def input_specs(self, shape: ShapeConfig):
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        f = jnp.float32
        if shape.kind == "train":
            return {
                "frames": jax.ShapeDtypeStruct((B, S, cfg.frontend_dim), f),
                "tokens": jax.ShapeDtypeStruct((B, S), i32),
                "labels": jax.ShapeDtypeStruct((B, S), i32),
            }
        if shape.kind == "prefill":
            return {
                "frames": jax.ShapeDtypeStruct((B, S, cfg.frontend_dim), f),
                "tokens": jax.ShapeDtypeStruct((B, 1024), i32),
            }
        return {"tokens": jax.ShapeDtypeStruct((B, 1), i32)}


def build_model(cfg: ModelConfig, ax: AxisRules, *, remat: str = "none"):
    if cfg.encoder_layers:
        return EncDec(cfg, ax, remat=remat)
    return LM(cfg, ax, remat=remat)
