"""Whether what the timed path served is correct.

Once the window has closed and the engine is freed, a sample of the
requests it finished, drawn from the seed, with the longest among them, is
run through the plain reference (``bench/refs/<reference>.py``): one full
causal forward over each prompt and its served tokens.  At every position
where the engine served a token, the reference's best logit minus its logit
for the served token is a gap; a greedy engine that computes what the model
states serves the reference's best token up to rounding, so the gap is 0 or
the size of a near-tie.  Two numbers are read over the sample: the widest
gap and the mean gap.  The mix's file (``check``) names those compared and
their limits, set as ``PERF.md`` records from sound runs of the program and
from the control.  A mixture of experts compares the mean: rounding alone
routes a near-tied token to another expert, and the change reaches later
positions through the KV cache, so its widest gap reads as high in sound
runs as in the control's.

The control is the reference computed in float8 (``quant="fp8"``): at the
same positions it reads the gap of the token that float8 puts first.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from bench.loop import Window


@dataclass
class Sample:
    seqs: List[np.ndarray]     # prompt + served tokens but the last
    rows: List[np.ndarray]     # positions whose next token was served
    served: List[np.ndarray]
    requests: int
    tokens: int


def sample(w: Window, seed: int, served_tokens: int,
           max_requests: int) -> Sample:
    """The finished requests to check: the longest (prompt and output), then
    others in an order drawn from the seed, until ``served_tokens`` tokens
    or ``max_requests`` requests."""
    done = [r for r in w.requests
            if r.finished is not None and r.finished <= w.close]
    if not done:
        raise RuntimeError("no request finished in the run")
    longest = max(done, key=lambda r: (r.prompt_len + len(r.handle.tokens),
                                       r.index))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 2])
    picked = [longest] + [rest[i] for i in rng.permutation(len(rest))]
    seqs, rows, served, n = [], [], [], 0
    for r in picked[:max_requests]:
        out = np.asarray(r.handle.tokens, np.int32)
        prompt = np.asarray(r.handle.prompt, np.int32)
        seqs.append(np.concatenate([prompt, out[:-1]]))
        rows.append(np.arange(len(prompt) - 1, len(prompt) - 1 + len(out)))
        served.append(out)
        n += len(out)
        if n >= served_tokens:
            break
    return Sample(seqs, rows, served, len(seqs), n)


def reference(conf):
    return importlib.import_module(f"bench.refs.{conf['reference']}")


def gaps(logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Each position's best logit minus the logit of ``tokens``' token."""
    picked = np.take_along_axis(logits, tokens[:, None].astype(np.int64), -1)
    return logits.max(-1) - picked[:, 0]


NUMBERS = ("max_logit_gap", "mean_logit_gap")


@dataclass
class Reading:
    max_logit_gap: float
    mean_logit_gap: float
    tokens: int
    requests: int
    worst: Dict[str, float]
    control: Optional["Reading"] = None
    raw: Optional[Dict[str, np.ndarray]] = None   # per position, for tools


def _reading(g: np.ndarray, s: Sample) -> Reading:
    at = int(np.argmax(g))
    req = int(np.searchsorted(np.cumsum([len(t) for t in s.served]), at,
                              side="right"))
    return Reading(float(g.max()), float(g.mean()), s.tokens, s.requests,
                   {"request": req, "gap": float(g[at])}, raw={"gap": g})


def check(conf, dims, seed: int, s: Sample, control: bool = False) -> Reading:
    """The gaps at every served position of the sample; with ``control``,
    also the float8 control's reading at the same positions."""
    ref = reference(conf)
    want = ref.logits(conf, dims, seed, s.seqs, s.rows)
    r = _reading(np.concatenate([gaps(lg, t)
                                 for lg, t in zip(want, s.served)]), s)
    if control:
        low = ref.logits(conf, dims, seed, s.seqs, s.rows, quant="fp8")
        r.control = _reading(np.concatenate([
            gaps(lg, lo.argmax(-1)) for lg, lo in zip(want, low)]), s)
        r.raw["control_gap"] = r.control.raw["gap"]
    return r


def verdict(r: Reading, limits: Dict[str, float]) -> Dict[str, Dict]:
    """Each number the mix compares, beside its limit."""
    return {n: {"value": getattr(r, n), "limit": limits[n]}
            for n in NUMBERS if n in limits}
