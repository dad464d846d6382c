"""Random model weights drawn from the seed, on the device, in the dtype
served.

Each layer's leaves come from keys folded from (seed, layer, leaf), so the
engine's weights, drawn for every layer at once in one jitted call, and the
reference's, drawn again one layer at a time after the engine is gone, are
the same numbers.  The layout is this benchmark's own: the engine adapter
maps it onto the program's parameter tree, and the reference reads it
directly.

Scales follow a fan-in initialisation: a projection's entries have standard
deviation 1/sqrt(fan_in), embedding, head and router 0.02.  Each RMSNorm
weight is 1 + delta, and the leaf holds delta (standard deviation 0.1), so
the norms' weights are exercised too.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.shapes import Dims

NORM_STD = 0.1
TABLE_STD = 0.02


def seed32(seed: int) -> np.uint32:
    """A 32-bit key seed from any whole number (the driver's exceed 2**31)."""
    return np.random.SeedSequence(int(seed)).generate_state(1, np.uint32)[0]


def layer_leaves(m: Dims) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Name -> (shape, standard deviation) of one layer's leaves."""
    d, H, K, hd, ff = m.d, m.heads, m.kv_heads, m.head_dim, m.ff
    out = {
        "attn_norm": ((d,), NORM_STD),
        "wq": ((d, H, hd), d ** -0.5),
        "wk": ((d, K, hd), d ** -0.5),
        "wv": ((d, K, hd), d ** -0.5),
        "wo": ((H, hd, d), (H * hd) ** -0.5),
        "mlp_norm": ((d,), NORM_STD),
    }
    if m.experts:
        E = m.experts
        out.update({"router": ((d, E), TABLE_STD),
                    "w_gate": ((E, d, ff), d ** -0.5),
                    "w_up": ((E, d, ff), d ** -0.5),
                    "w_down": ((E, ff, d), ff ** -0.5)})
    else:
        out.update({"w_gate": ((d, ff), d ** -0.5),
                    "w_up": ((d, ff), d ** -0.5),
                    "w_down": ((ff, d), ff ** -0.5)})
    return out


def top_leaves(m: Dims) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    return {"embed": ((m.vocab, m.d), TABLE_STD),
            "final_norm": ((m.d,), NORM_STD),
            "head": ((m.d, m.vocab), TABLE_STD)}


def _draw(key, leaves, dtype):
    return {name: (jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.float32) * std).astype(dtype)
            for i, (name, (shape, std)) in enumerate(sorted(leaves.items()))}


def _layer_key(seed, layer):
    return jax.random.fold_in(jax.random.PRNGKey(seed), layer)


def draw_layer(m: Dims, seed, layer, dtype):
    """One layer's leaves (traceable: ``seed`` and ``layer`` may be traced)."""
    return _draw(_layer_key(seed, layer), layer_leaves(m), dtype)


TOP = 1 << 30   # the key index of the leaves outside the layers


def draw_top(m: Dims, seed, dtype):
    """Embedding, final norm and head."""
    return _draw(_layer_key(seed, TOP), top_leaves(m), dtype)


def draw_all(m: Dims, seed, dtype):
    """Every leaf: ``{"layers": leaves stacked over layers, **top}``."""
    layers = jax.vmap(lambda i: draw_layer(m, seed, i, dtype))(
        jnp.arange(m.layers))
    return {"layers": layers, **draw_top(m, seed, dtype)}


@functools.lru_cache(maxsize=None)
def layer_fn(m: Dims, dtype):
    """A jitted draw of one layer, for the reference."""
    return jax.jit(lambda seed, layer: draw_layer(m, seed, layer, dtype))


@functools.lru_cache(maxsize=None)
def top_fn(m: Dims, dtype):
    return jax.jit(lambda seed: draw_top(m, seed, dtype))
