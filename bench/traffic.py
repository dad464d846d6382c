"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and draws its requests from the seed.

Every seed gets the same requests: the sizes and the gaps between
arrivals are the distributions' quantiles at (i + 0.5) / n, put in one
fixed order (``ORDER_SEED``).  What ``--seed`` changes is
the token ids (and, elsewhere, the weights and the sample checked).  An
order drawn from ``--seed`` would move a tail over some tens or hundreds of
requests by 10-20% from seed to seed (PERF.md), more than any change a
benchmark should resolve.

A mix is one of two loops:

- ``open``: Poisson arrivals at ``rate_rps``, independent of the system.
  Arrivals start ``lead_s`` seconds before the window opens, so the window
  opens on a system already in its steady state.
- ``closed``: a backlog of ``depth`` requests waits at all times; a request
  is queued as soon as one is taken.  The window opens once every slot of
  the engine holds a request.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

ORDER_SEED = 0


@dataclass
class Request:
    index: int
    due: float            # seconds after the first arrival (open loop)
    prompt: np.ndarray    # int32 token ids
    output_len: int


@dataclass
class Schedule:
    loop: str             # "open" | "closed"
    lead_s: float         # open loop: arrivals before the window opens
    depth: int            # closed loop: requests kept waiting
    requests: List[Request]


def quantiles(dist: Dict[str, Any], n: int) -> np.ndarray:
    """The distribution's quantiles at (i + 0.5) / n, before clipping."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        return dist["median"] * np.exp(dist["sigma"] * z)
    if kind == "uniform":
        return dist["min"] + u * (dist["max"] - dist["min"])
    raise ValueError(f"unknown length distribution {kind!r}")


def lengths(traffic: Dict[str, Any], n: int, rng: np.random.Generator):
    """(prompt, output) lengths of ``n`` requests: each set clipped to its
    [min, max], permuted, and each output clipped so that prompt + output
    fits ``max_total``."""
    p, o = traffic["prompt"], traffic["output"]
    prompts = np.clip(np.rint(quantiles(p, n)), p["min"], p["max"])
    outputs = np.clip(np.rint(quantiles(o, n)), o["min"], o["max"])
    prompts = rng.permutation(prompts).astype(int)
    outputs = rng.permutation(outputs).astype(int)
    outputs = np.minimum(outputs, traffic["max_total"] - prompts)
    if np.any(outputs < 1):
        raise ValueError("max_total leaves no room for an output")
    return prompts, outputs


def arrival_gaps(rate: float, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` exponential gaps of mean 1 / rate: quantiles, permuted."""
    u = (np.arange(n) + 0.5) / n
    return rng.permutation(-np.log1p(-u) / rate)


def schedule(traffic: Dict[str, Any], vocab: int, seed: int,
             seconds: float) -> Schedule:
    """The requests of one run of ``seconds`` seconds."""
    rng = np.random.default_rng([ORDER_SEED, 0])
    loop = traffic["loop"]
    if loop == "open":
        lead = float(traffic["lead_s"])
        n = math.ceil(traffic["rate_rps"] * (lead + seconds)) + 1
        gaps = arrival_gaps(traffic["rate_rps"], n, rng)
        due = np.concatenate([[0.0], np.cumsum(gaps[1:])])
        depth = 0
    elif loop == "closed":
        lead, n, depth = 0.0, int(traffic["requests"]), int(traffic["depth"])
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown loop {loop!r}")
    prompts, outputs = lengths(traffic, n, rng)
    ids = np.random.default_rng([seed, 1])
    reqs = [Request(i, float(due[i]),
                    ids.integers(0, vocab, int(prompts[i]), dtype=np.int32),
                    int(outputs[i]))
            for i in range(n)]
    return Schedule(loop, lead, depth, reqs)
