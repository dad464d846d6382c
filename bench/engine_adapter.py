"""The one file of the benchmark that touches the program: MiniEngine
(``src/repro/serving/engine.py``) and its model configuration.

``MiniEngine.run()`` drains its queue and returns, and the engine has no
public step, so ``Adapter.step`` runs the body of ``run()``'s loop:
``_admit()`` (each waiting request's prefill and its insert into the slot
cache) and then ``_decode_step()``.  Around each call it records a host span
and writes a ``TraceAnnotation`` of the same kind and id, so that the
profiler's device events can be matched to the call that issued them.

The weights are the benchmark's (``bench/weights.py``), drawn on the device
in one jitted call and mapped onto the program's parameter tree here.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.shapes import Dims, dims
from bench.spec import ROOT

sys.path.insert(0, str(ROOT / "src"))

from repro.configs import get_config  # noqa: E402
from repro.serving import engine as engine_mod  # noqa: E402
from repro.serving.engine import MiniEngine  # noqa: E402

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def model_config(conf: Dict[str, Any]):
    """The program's ModelConfig for a configuration file: the registered
    model, at the file's depth and norm epsilon; MoE capacity from the
    file.  A width that differs from the file is an error."""
    base = get_config(conf["program_model"])
    cfg = dataclasses.replace(base, num_layers=conf["num_hidden_layers"],
                              rms_eps=conf["rms_norm_eps"],
                              rope_theta=conf["rope_theta"])
    m = dims(conf)
    have = dict(d=cfg.d_model, heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                head_dim=cfg.resolved_head_dim, vocab=cfg.vocab_size,
                gated=cfg.gated_mlp, act=cfg.mlp_act, tied=cfg.tie_embeddings)
    want = dict(d=m.d, heads=m.heads, kv_heads=m.kv_heads, head_dim=m.head_dim,
                vocab=m.vocab, gated=True, act=conf["hidden_act"],
                tied=conf["tie_word_embeddings"])
    if m.experts:
        moe = dataclasses.replace(
            cfg.moe, capacity_factor_eval=float(
                conf["serving"]["moe_capacity_factor"]))
        cfg = dataclasses.replace(cfg, moe=moe)
        have.update(ff=moe.expert_d_ff, experts=moe.num_experts, k=moe.top_k)
        want.update(ff=m.ff, experts=m.experts, k=m.top_k)
    else:
        have.update(ff=cfg.d_ff, experts=0)
        want.update(ff=m.ff, experts=0)
    if have != want or cfg.padded_vocab != cfg.vocab_size:
        raise ValueError(f"{conf['name']}: the program's model {have} is not "
                         f"the configuration's {want}")
    return cfg


def program_params(m: Dims, seed, dtype):
    """The benchmark's weights in the program's parameter tree."""
    w = weights.draw_all(m, seed, dtype)
    L = w["layers"]
    ffn = {"w_in": L["w_up"], "w_gate": L["w_gate"], "w_out": L["w_down"]}
    block = {"ln1": L["attn_norm"], "ln2": L["mlp_norm"],
             "attn": {k: L[k] for k in ("wq", "wk", "wv", "wo")}}
    if m.experts:
        block["moe"] = {"router": L["router"], **ffn}
    else:
        block["mlp"] = ffn
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "head": w["head"], "groups": (block,), "tail": ()}


@dataclass
class Span:
    kind: str             # "prefill" | "decode"
    id: int
    t0: float
    t1: float
    info: Dict[str, Any] = field(default_factory=dict)


class Adapter:
    """MiniEngine for one configuration, driven one step at a time."""

    def __init__(self, conf: Dict[str, Any], seed: int):
        serving = conf["serving"]
        self.dims = dims(conf)
        self.dtype = DTYPES[serving["dtype"]]
        self.max_seq = int(serving["max_seq"])
        self.cfg = model_config(conf)
        draw = jax.jit(lambda s: program_params(self.dims, s, self.dtype))
        t = time.perf_counter()
        params = draw(weights.seed32(seed))
        jax.block_until_ready(params)
        self.weights_s = time.perf_counter() - t
        t = time.perf_counter()
        self.engine = MiniEngine(self.cfg, max_slots=int(serving["slots"]),
                                 max_seq=self.max_seq, params=params,
                                 dtype=self.dtype)
        want = jax.tree_util.tree_map(
            lambda s: (s.shape, s.dtype),
            engine_mod.shape_tree(self.engine.model.pds(), self.dtype))
        got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
        if got != want:
            raise ValueError("the benchmark's weights do not fit the "
                             "program's parameter tree")
        jax.block_until_ready(self.engine.cache)
        self.engine_s = time.perf_counter() - t
        self.slots = int(serving["slots"])
        self.spans: List[Span] = []
        self._prefill = self.engine._prefill
        self.engine._prefill = self._traced_prefill
        self._admitted: List[Any] = []

    # -------------------------------------------------------------- shapes --
    def bucket(self, prompt_len: int) -> int:
        return min(engine_mod._bucket(prompt_len), self.max_seq)

    def warm_up(self, prompt_lens: Sequence[int]) -> int:
        """Compile every program the given prompts use: each prefill bucket,
        the insert and the decode step.  Returns the number of buckets."""
        buckets = sorted({self.bucket(n) for n in prompt_lens})
        rng = np.random.default_rng(0)
        for b in buckets:
            n = min(b, self.max_seq - 2)
            self.engine.submit([rng.integers(0, self.dims.vocab, n)], 2)
        self.engine.run()
        self.engine.step_log.clear()
        self.spans.clear()
        return len(buckets)

    # ---------------------------------------------------------------- load --
    def submit(self, prompt: np.ndarray, max_new_tokens: int):
        return self.engine.submit([prompt], max_new_tokens)[0]

    @property
    def n_waiting(self) -> int:
        return len(self.engine.waiting)

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.engine.slots)

    def busy(self) -> bool:
        return bool(self.engine.waiting) or self.n_active > 0

    # ---------------------------------------------------------------- step --
    def _traced_prefill(self, req, slot: int) -> None:
        others = self.n_active
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("prefill", id=len(self.spans)):
            self._prefill(req, slot)
        self.spans.append(Span("prefill", len(self.spans), t0,
                               time.perf_counter(),
                               {"rid": req.rid, "tokens": len(req.prompt),
                                "bucket": self.bucket(len(req.prompt)),
                                "decoding": others}))
        self._admitted.append(req)

    def step(self) -> List[Tuple[Any, float, int]]:
        """One pass of ``run()``'s loop.  Returns (request, time, tokens so
        far) for every token emitted: a prefill's first token and each
        active slot's decoded token."""
        eng = self.engine
        self._admitted = []
        eng._admit()
        out = [(r, r.first_token, 1) for r in self._admitted]
        active = [(i, s) for i, s in enumerate(eng.slots) if s is not None]
        if not active:
            return out
        ctx = [int(eng.slot_pos[i]) + 1 for i, _ in active]
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("decode", id=len(self.spans)):
            eng._decode_step()
        t1 = time.perf_counter()
        self.spans.append(Span("decode", len(self.spans), t0, t1,
                               {"batch": len(active), "contexts": ctx}))
        out.extend((r, t1, len(r.tokens)) for _, r in active)
        return out

    # ------------------------------------------------------------- teardown --
    def reset(self) -> None:
        """Drop every request, waiting or in a slot (between sweep points)."""
        self.engine.waiting.clear()
        self.engine.slots = [None] * self.slots
        self.spans.clear()

    def close(self) -> None:
        """Free the engine's weights and cache on the device."""
        eng = self.engine
        eng.params = eng.cache = None
        eng._prefill_jit = eng._decode_jit = eng._insert_jit = None
        self.engine = None
        gc.collect()


def compile_for(conf: Dict[str, Any], sharding, buckets: Sequence[int]):
    """Compile the engine's programs for a described device (no arrays):
    the weight draw, a prefill per bucket, the insert and the decode step.
    Returns name -> compiled program and the bytes of weights and cache."""
    serving = conf["serving"]
    m, dtype = dims(conf), DTYPES[serving["dtype"]]
    slots, max_seq = int(serving["slots"]), int(serving["max_seq"])
    model = engine_mod.build_model(model_config(conf),
                                   engine_mod.AxisRules(None))

    def on(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=sharding), tree)

    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=sharding)
    params = on(engine_mod.shape_tree(model.pds(), dtype))
    cache = on(engine_mod.shape_tree(model.cache_pds(slots, max_seq), dtype))
    one = on(engine_mod.shape_tree(model.cache_pds(1, max_seq), dtype))
    out = {"weights": jax.jit(lambda s: program_params(m, s, dtype)).lower(
        jax.ShapeDtypeStruct((), jnp.uint32, sharding=sharding)).compile()}
    prefill = jax.jit(functools.partial(engine_mod.prefill_step, model,
                                        max_seq))
    for b in buckets:
        out[f"prefill_{b}"] = prefill.lower(params, i32(1, b),
                                            i32(1)).compile()
    out["insert"] = jax.jit(engine_mod._insert_slot, donate_argnums=0).lower(
        cache, one, i32()).compile()
    out["decode"] = jax.jit(functools.partial(engine_mod.decode_step, model),
                            donate_argnums=1).lower(
        params, cache, i32(slots, 1), i32(slots)).compile()
    nbytes = lambda t: sum(  # noqa: E731
        s.size * s.dtype.itemsize for s in jax.tree_util.tree_leaves(t))
    return out, nbytes(params), nbytes(cache)
