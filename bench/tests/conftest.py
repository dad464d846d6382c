"""The benchmark's own tests, on the CPU at a tiny size:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The harness's look for a chip and its peaks table are replaced here, and the
program's model is its smoke-width variant; the rest of a run is the real
one: weights from the seed, MiniEngine, the loop, the trace reduction and
the reference check.
"""
import dataclasses
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def pytest_configure(config):
    # CPU programs are not worth caching, and a cache entry written for one
    # CPU can warn when read on another
    import jax
    jax.config.update("jax_enable_compilation_cache", False)


def tiny_conf(moe: bool):
    conf = {"name": "tiny-moe" if moe else "tiny", "hidden_act": "silu",
            "hidden_size": 64, "intermediate_size": 64 if moe else 128,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 512,
            "rms_norm_eps": 1e-5, "rope_theta": 1e6,
            "tie_word_embeddings": False,
            "serving": {"slots": 4, "max_seq": 128, "dtype": "bfloat16",
                        "moe_capacity_factor": 4.0},
            "program_model": "mixtral-8x7b" if moe else "qwen2-7b",
            "reference": "llama_decoder"}
    if moe:
        conf.update(num_local_experts=4, num_experts_per_tok=2)
    return conf


def tiny_traffic(loop: str, limit: float = 0.5,
                 number: str = "max_logit_gap"):
    return {"loop": loop, "rate_rps": 20.0, "lead_s": 0.3,
            "depth": 4,
            "requests": 400,
            "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                       "min": 8, "max": 100},
            "output": {"dist": "lognormal", "median": 10, "sigma": 0.8,
                       "min": 2, "max": 40},
            "max_total": 128,
            "check": {"served_tokens": 200, "max_requests": 16,
                      number: limit}}


@pytest.fixture
def tiny(monkeypatch):
    """Patch the harness to run a tiny cell on the CPU; returns a function
    (moe, loop, limit, number) -> cell that sets which one."""
    import jax
    from bench import engine_adapter, run, spec
    from repro.configs import get_config

    def smoke(name):
        c = get_config(name, smoke=True)
        # the smoke MoE attends through a 16-token window; the tiny
        # configuration, as Mixtral's, attends to every earlier position
        return dataclasses.replace(c, sliding_window=1 << 20) \
            if c.moe else c

    monkeypatch.setattr(engine_adapter, "get_config", smoke)
    monkeypatch.setattr(run, "devices_for", lambda chips: jax.devices())
    monkeypatch.setattr(run, "peaks_for", lambda d: {
        "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    monkeypatch.setattr(run, "enable_cache", lambda: "off")
    real = spec.load_cell("mixtral-8x7b.chat")

    def use(moe=False, loop="open", limit=0.5, number="max_logit_gap"):
        cell = spec.Cell("tiny", tiny_conf(moe),
                         tiny_traffic(loop, limit, number), 1,
                         real.end_to_end, real.per_layer)
        monkeypatch.setattr(spec, "load_cell", lambda name: cell)
        return cell
    return use
