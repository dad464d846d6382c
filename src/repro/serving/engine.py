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

Telemetry: with a ``repro.obs.Telemetry`` attached (``telemetry=`` or the
attribute, at any time), each pass records spans on the host clock -- the
request's ``queue_wait``, ``admit``, each ``prefill`` and ``decode`` call and
the phases inside them (inputs, program, insert or bookkeeping) -- and
counters: waiting requests and active slots per pass, each step program's
compile count when it changes, and per call of an MoE program the rows its
expert GEMMs compute (as the MoE layer reported them when it was traced)
against the rows its real tokens need.  Every span but ``queue_wait`` is
also a ``jax.profiler.TraceAnnotation`` named ``engine.<kind>``, with its
meta and counters as stats, so it shares the device trace's clock.  With no
telemetry attached the engine makes no recorder or profiler call.
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
from repro.obs.telemetry import Telemetry


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


def _named(name: str, fn, *args):
    """``fn`` with ``args`` bound, under a stable name: the program that
    ``jax.jit`` makes of it is ``jit_<name>`` in a profile."""
    bound = functools.partial(fn, *args)
    bound.__name__ = name
    return bound


class _Span:
    """A span being recorded: the recorder's entry and the profiler
    annotation ``engine.<kind>`` (scalar meta as its stats), open until
    ``close``.  ``open_spans`` lists the spans open now, innermost last."""

    def __init__(self, tel: Telemetry, open_spans: List["_Span"], kind: str,
                 rid: int = -1, parent: Optional[int] = None, **meta):
        self.tel, self.open_spans = tel, open_spans
        self.rid, self.parent = rid, parent
        self.ann = jax.profiler.TraceAnnotation(f"engine.{kind}", **meta)
        self.ann.__enter__()
        self.start = time.perf_counter()
        self.id = tel.span(kind, rid, self.start, self.start, parent=parent,
                           **meta)
        open_spans.append(self)

    def close(self, **meta) -> None:
        """End the span; ``meta`` (the counters of the call) rides on it."""
        self.open_spans.remove(self)
        self.tel.close_span(self.id, time.perf_counter(), **meta)
        if meta:
            self.ann.set_metadata(**meta)
        self.ann.__exit__(None, None, None)

    def next(self, kind: str) -> "_Span":
        """Close this span and open the next phase under the same parent."""
        self.close()
        return _Span(self.tel, self.open_spans, kind, self.rid, self.parent)


def _closing_spans(step):
    """An engine pass's step that, when it raises, leaves no span open:
    each is ended, innermost first, and the exception goes on."""
    @functools.wraps(step)
    def wrapped(self):
        try:
            return step(self)
        finally:
            while self._open:
                self._open[-1].close()
    return wrapped


class MiniEngine:
    def __init__(self, cfg: ModelConfig, *, max_slots: int = 8,
                 max_seq: int = 256, seed: int = 0,
                 params=None, dtype=jnp.bfloat16,
                 telemetry: Optional[Telemetry] = None):
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
        self.telemetry = telemetry
        self._admit_span: Optional[int] = None   # parent of its prefills
        self._open: List[_Span] = []             # spans open now
        self._compiled: Dict[str, int] = {}      # program -> compile count

        # the slot cache is donated, so it is updated in place, not copied;
        # each program is this engine's own, so its compile count is too
        self._prefill_jit = jax.jit(
            _named("engine_prefill", prefill_step, self.model, max_seq))
        self._decode_jit = jax.jit(
            _named("engine_decode", decode_step, self.model),
            donate_argnums=1)
        self._insert_jit = jax.jit(_named("_insert_slot", _insert_slot),
                                   donate_argnums=0)

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
    def _compiles(self, name: str, program) -> Dict[str, int]:
        """Telemetry on: the compile count of step program ``name`` where it
        changed since the last call, recorded and returned to ride on the
        call's span."""
        n = program._cache_size()
        if self._compiled.get(name) == n:
            return {}
        self._compiled[name] = n
        self.telemetry.counter(f"compiled.{name}", time.perf_counter(), n)
        return {"compiled": n}

    def _expert_rows(self, tokens: int, real: int) -> Dict[str, int]:
        """Telemetry on, an MoE model: the rows one MoE layer's expert
        GEMMs compute in a call of a program over ``tokens`` tokens, as
        the layer reported them while it was traced, and the rows its
        ``real`` tokens need; recorded and returned.  Empty for a dense
        model."""
        report = self.ax.notes.get(("moe_expert_rows", tokens))
        if report is None:
            return {}
        rows, k = report
        t = time.perf_counter()
        self.telemetry.counter("expert_rows", t, rows)
        self.telemetry.counter("expert_rows_needed", t, real * k)
        return {"expert_rows": rows, "expert_rows_needed": real * k}

    def _prefill(self, req: ServeRequest, slot: int) -> None:
        tel = self.telemetry
        S = len(req.prompt)
        bucket = min(_bucket(S), self.max_seq)
        if tel is not None:
            call = _Span(tel, self._open, "prefill", req.rid, self._admit_span,
                         tokens=S, bucket=bucket, slot=slot,
                         decoding=sum(s is not None for s in self.slots))
            tel.span("queue_wait", req.rid, req.submitted, call.start)
            phase = _Span(tel, self._open, "prefill.inputs", req.rid, call.id)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :S] = req.prompt
        # the first token comes from the TRUE last prompt position S-1
        # (causal masking makes it independent of the padding); pad KV
        # beyond S is never visible: decode masks t <= pos and each step
        # overwrites slot pos before it becomes attendable.
        t0 = time.perf_counter()
        toks, last = jnp.asarray(toks), jnp.asarray([S - 1], jnp.int32)
        if tel is not None:
            phase = phase.next("prefill.program")
        first, cache1 = self._prefill_jit(self.params, toks, last)
        first = int(first[0])
        dt = time.perf_counter() - t0
        self.step_log.append({"kind": "prefill", "tokens": int(S), "dur": dt})
        if tel is not None:
            counters = {**self._compiles("engine_prefill", self._prefill_jit),
                        **self._expert_rows(bucket, S)}
            phase = phase.next("prefill.insert")
        self.cache = self._insert_jit(self.cache, cache1, jnp.int32(slot))
        if tel is not None:
            phase.close(**self._compiles("_insert_slot", self._insert_jit))
        now = time.perf_counter()
        req.first_token = now
        req.tokens.append(first)
        self.slots[slot] = req
        self.slot_pos[slot] = S
        self.slot_tok[slot] = first
        if tel is not None:
            call.close(**counters)

    @_closing_spans
    def _admit(self) -> None:
        tel = self.telemetry
        if tel is not None:
            free = sum(s is None for s in self.slots)
            t = time.perf_counter()
            tel.counter("waiting", t, len(self.waiting))
            tel.counter("active_slots", t, self.max_slots - free)
            span = _Span(tel, self._open, "admit", waiting=len(self.waiting),
                         free=free)
            self._admit_span = span.id
        for i in range(self.max_slots):
            if self.slots[i] is None and self.waiting:
                req = self.waiting.pop(0)
                self._prefill(req, i)
        if tel is not None:
            span.close()
            self._admit_span = None

    @_closing_spans
    def _decode_step(self) -> None:
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            return
        tel = self.telemetry
        if tel is not None:
            ctx = self.slot_pos[active] + 1
            call = _Span(tel, self._open, "decode", batch=len(active),
                         ctx_sum=int(ctx.sum()), ctx_max=int(ctx.max()))
            phase = _Span(tel, self._open, "decode.inputs", parent=call.id)
        toks = jnp.asarray(self.slot_tok.reshape(-1, 1))
        pos = jnp.asarray(self.slot_pos)
        t0 = time.perf_counter()
        if tel is not None:
            phase = phase.next("decode.program")
        nxt, self.cache = self._decode_jit(self.params, self.cache, toks, pos)
        nxt = np.asarray(nxt)
        dt = time.perf_counter() - t0
        self.step_log.append({"kind": "decode", "batch": len(active), "dur": dt})
        now = time.perf_counter()
        if tel is not None:
            phase = phase.next("decode.bookkeep")
        for i in active:
            req = self.slots[i]
            req.tokens.append(int(nxt[i]))
            self.slot_pos[i] += 1
            self.slot_tok[i] = int(nxt[i])
            if (len(req.tokens) >= req.max_new_tokens
                    or self.slot_pos[i] >= self.max_seq - 1):
                req.finished = now
                self.slots[i] = None
        if tel is not None:
            phase.close()
            call.close(**self._compiles("engine_decode", self._decode_jit),
                       **self._expert_rows(self.max_slots, len(active)))

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
