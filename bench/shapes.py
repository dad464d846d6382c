"""Operations and bytes that one engine call needs, from the configuration's
shapes.

"Needs" means the work of the request itself: real prompt tokens and not
the bucket's padding, the experts a token is routed to (top-k) and not every
expert, each slot's live context and not ``max_seq``, only the slots that
hold a request.  A program that pads, computes every expert or attends over
the whole cache does more than this, so a share of a peak computed from it
cannot pass 100% unless the device time leaves out part of the call.

Counts are multiply-adds times two.  Weights and the KV cache are read in
the dtype served (``bytes_per_param``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Sequence


@dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    layers: int
    experts: int        # 0 for a dense FFN
    top_k: int
    bytes_per_param: int

    @property
    def attn_params(self) -> int:
        """q, k, v and output projections of one layer."""
        return self.d * (self.heads + 2 * self.kv_heads) * self.head_dim \
            + self.heads * self.head_dim * self.d

    @property
    def expert_params(self) -> int:
        """One gated FFN: gate, up and down projections."""
        return 3 * self.d * self.ff

    @property
    def ffn_active_params(self) -> int:
        """FFN weights one token multiplies by, the router included."""
        if self.experts:
            return self.top_k * self.expert_params + self.d * self.experts
        return self.expert_params


def dims(conf: Dict[str, Any]) -> Dims:
    d, heads = conf["hidden_size"], conf["num_attention_heads"]
    return Dims(d=d, heads=heads, kv_heads=conf["num_key_value_heads"],
                head_dim=conf.get("head_dim") or d // heads,
                ff=conf["intermediate_size"], vocab=conf["vocab_size"],
                layers=conf["num_hidden_layers"],
                experts=conf.get("num_local_experts", 0),
                top_k=conf.get("num_experts_per_tok", 0),
                bytes_per_param={"bfloat16": 2, "float32": 4}[
                    conf["serving"]["dtype"]])


def experts_used(m: Dims, tokens: int) -> float:
    """Expected number of distinct experts that ``tokens`` tokens, each
    routed to top_k of the experts uniformly, reach."""
    if not m.experts:
        return 0.0
    return m.experts * (1.0 - (1.0 - m.top_k / m.experts) ** tokens)


def layer_weight_bytes(m: Dims, tokens: int) -> float:
    """Weights of one layer that ``tokens`` tokens read: attention, norms
    and the dense FFN, or the router and the experts they reach."""
    ffn = (experts_used(m, tokens) * m.expert_params + m.d * m.experts
           if m.experts else m.expert_params)
    return (m.attn_params + ffn + 2 * m.d) * m.bytes_per_param


def prefill(m: Dims, prompt_len: int) -> Dict[str, float]:
    """One request's prefill of ``prompt_len`` real tokens, writing its KV
    and computing logits for its last position only."""
    S = prompt_len
    attn = 4 * m.heads * m.head_dim * S * (S + 1) / 2   # causal QK^T and PV
    per_layer = 2 * S * (m.attn_params + m.ffn_active_params) + attn
    flops = m.layers * per_layer + 2 * m.d * m.vocab
    kv_write = 2 * S * m.kv_heads * m.head_dim * m.bytes_per_param
    bytes_ = (m.layers * (layer_weight_bytes(m, S) + kv_write)
              + m.d * m.vocab * m.bytes_per_param)
    return {"flops": float(flops), "bytes": float(bytes_)}


def decode(m: Dims, contexts: Sequence[int]) -> Dict[str, float]:
    """One decode step of the slots that hold a request; ``contexts`` are
    their live context lengths, the new token included."""
    B = len(contexts)
    ctx = float(sum(contexts))
    flops = (m.layers * (2 * B * (m.attn_params + m.ffn_active_params)
                         + 4 * m.heads * m.head_dim * ctx)
             + 2 * B * m.d * m.vocab)
    kv = 2 * m.kv_heads * m.head_dim * m.bytes_per_param
    bytes_ = (m.layers * (layer_weight_bytes(m, B) + kv * ctx)
              + m.d * m.vocab * m.bytes_per_param)
    return {"flops": float(flops), "bytes": float(bytes_)}
