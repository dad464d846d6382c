"""MiniEngine: a real (executing) continuous-batching serving engine in JAX.

This is the measured system for the paper's Table-2 protocol: the simulator
predicts its throughput; bench_e2e_accuracy compares.  It runs in bf16 at
qwen2-7b's published widths on one TPU v5e (depth cut to fit; see
``chip_smoke.py``) and at smoke widths on the CPU; ``launch/serve.py``
drives it.

Design (vLLM-like, slot-based):
- a fixed pool of `max_slots` sequence slots with a shared stacked KV cache
  (the JAX analogue of a paged KV pool with page == slot);
- prefill runs per-request (padded to length buckets to bound compiles) and
  its KV is scattered into the slot cache;
- decode steps run the whole active slot set with per-slot positions;
- slots free on completion; waiting requests admit immediately (continuous
  batching).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.common import AxisRules, init_tree, shape_tree
from repro.models.model import build_model


@dataclass
class ServeRequest:
    rid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int
    submitted: float = 0.0
    first_token: Optional[float] = None
    finished: Optional[float] = None
    tokens: List[int] = field(default_factory=list)


# The engine's device steps.  Each returns greedy token ids, never logits:
# only (B,) ints cross to the host.
def prefill_step(model, max_seq: int, params, tokens, last):
    """One request's prefill: its first token (from position ``last``) and
    its KV cache sized ``max_seq``."""
    logits, cache = model.prefill(params, {"tokens": tokens},
                                  cache_len=max_seq, last=last)
    return jnp.argmax(logits[:, 0], -1), cache


def decode_step(model, params, cache, tokens, pos):
    """One decode step over every slot."""
    logits, cache = model.decode(params, cache, tokens, pos)
    return jnp.argmax(logits[:, 0], -1), cache


def _insert_slot(cache, one, slot):
    """Write a one-request prefill cache into row ``slot`` of the slot
    cache (batch is axis 1 of the scanned group leaves, 0 of the tail)."""
    def put(axis):
        return lambda c_all, c_one: jax.lax.dynamic_update_slice_in_dim(
            c_all, c_one.astype(c_all.dtype), slot, axis=axis)
    return {"groups": jax.tree_util.tree_map(put(1), cache["groups"],
                                             one["groups"]),
            "tail": jax.tree_util.tree_map(put(0), cache["tail"],
                                           one["tail"])}


def _bucket(n: int) -> int:
    b = 16
    while b < n:
        b *= 2
    return b


class MiniEngine:
    def __init__(self, cfg: ModelConfig, *, max_slots: int = 8,
                 max_seq: int = 256, seed: int = 0,
                 params=None, dtype=jnp.bfloat16):
        self.cfg = cfg
        self.ax = AxisRules(None)
        self.model = build_model(cfg, self.ax)
        self.max_slots = max_slots
        self.max_seq = max_seq
        if params is None:
            params = init_tree(jax.random.PRNGKey(seed), self.model.pds(), dtype)
        self.params = params
        cache_pds = self.model.cache_pds(max_slots, max_seq)
        self.cache = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            shape_tree(cache_pds, dtype))
        self.slots: List[Optional[ServeRequest]] = [None] * max_slots
        self.slot_pos = np.zeros(max_slots, np.int32)   # next write position
        self.slot_tok = np.zeros(max_slots, np.int32)   # last emitted token
        self.waiting: List[ServeRequest] = []
        self.step_log: List[Dict] = []
        self._next_rid = 0

        # the slot cache is donated, so it is updated in place, not copied
        self._prefill_jit = jax.jit(
            functools.partial(prefill_step, self.model, max_seq))
        self._decode_jit = jax.jit(functools.partial(decode_step, self.model),
                                   donate_argnums=1)
        self._insert_jit = jax.jit(_insert_slot, donate_argnums=0)

    # ------------------------------------------------------------- intake --
    def submit(self, prompts: List[np.ndarray], max_new_tokens: int) -> List[ServeRequest]:
        now = time.perf_counter()
        reqs = [ServeRequest(rid=self._next_rid + i,
                             prompt=np.asarray(p, np.int32),
                             max_new_tokens=max_new_tokens, submitted=now)
                for i, p in enumerate(prompts)]
        self._next_rid += len(reqs)
        self.waiting.extend(reqs)
        return reqs

    # ----------------------------------------------------------- internals --
    def _prefill(self, req: ServeRequest, slot: int) -> None:
        S = len(req.prompt)
        bucket = min(_bucket(S), self.max_seq)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :S] = req.prompt
        # the first token comes from the TRUE last prompt position S-1
        # (causal masking makes it independent of the padding); pad KV
        # beyond S is never visible: decode masks t <= pos and each step
        # overwrites slot pos before it becomes attendable.
        t0 = time.perf_counter()
        first, cache1 = self._prefill_jit(self.params, jnp.asarray(toks),
                                          jnp.asarray([S - 1], jnp.int32))
        first = int(first[0])
        dt = time.perf_counter() - t0
        self.step_log.append({"kind": "prefill", "tokens": int(S), "dur": dt})
        self.cache = self._insert_jit(self.cache, cache1, jnp.int32(slot))
        now = time.perf_counter()
        req.first_token = now
        req.tokens.append(first)
        self.slots[slot] = req
        self.slot_pos[slot] = S
        self.slot_tok[slot] = first

    def _admit(self) -> None:
        for i in range(self.max_slots):
            if self.slots[i] is None and self.waiting:
                req = self.waiting.pop(0)
                self._prefill(req, i)

    def _decode_step(self) -> None:
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            return
        toks = jnp.asarray(self.slot_tok.reshape(-1, 1))
        pos = jnp.asarray(self.slot_pos)
        t0 = time.perf_counter()
        nxt, self.cache = self._decode_jit(self.params, self.cache, toks, pos)
        nxt = np.asarray(nxt)
        dt = time.perf_counter() - t0
        self.step_log.append({"kind": "decode", "batch": len(active), "dur": dt})
        now = time.perf_counter()
        for i in active:
            req = self.slots[i]
            req.tokens.append(int(nxt[i]))
            self.slot_pos[i] += 1
            self.slot_tok[i] = int(nxt[i])
            if (len(req.tokens) >= req.max_new_tokens
                    or self.slot_pos[i] >= self.max_seq - 1):
                req.finished = now
                self.slots[i] = None

    # ---------------------------------------------------------------- run --
    def run(self) -> Dict[str, float]:
        t0 = time.perf_counter()
        served: List[ServeRequest] = list(self.waiting)
        while self.waiting or any(s is not None for s in self.slots):
            self._admit()
            self._decode_step()
        dur = time.perf_counter() - t0
        toks = sum(len(r.tokens) for r in served)
        ttfts = [r.first_token - r.submitted for r in served if r.first_token]
        tpots = [(r.finished - r.first_token) / max(len(r.tokens) - 1, 1)
                 for r in served if r.finished and r.first_token]
        return {
            "n_requests": len(served),
            "output_tokens": toks,
            "duration_s": dur,
            "throughput_tok_s": toks / dur,
            "ttft_mean_s": float(np.mean(ttfts)) if ttfts else float("nan"),
            "tpot_mean_s": float(np.mean(tpots)) if tpots else float("nan"),
            "decode_steps": sum(1 for s in self.step_log if s["kind"] == "decode"),
        }
