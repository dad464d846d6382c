"""End-to-end serving driver (the paper's Table-2 protocol).

The real MiniEngine serves a batch of seeded requests on the local device,
then the Frontier simulator predicts the same batch, replayed as a trace, on
that device's hardware profile:

    PYTHONPATH=src python -m repro.launch.serve --layers 24 --requests 16 \\
        --prompt-min 128 --prompt-max 1024 --output-len 64 --max-seq 2048

On a TPU the profile comes from ``DEVICE_KINDS`` by ``device_kind``, and a
chip missing from that table is an error.  On the CPU the profile is
micro-benchmarked on the host; pass ``--smoke`` for the reduced widths.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import ModelRef, OpModelSpec, PolicySpec, Report, SimSpec, \
    TopologySpec, WorkloadSpec
from repro.api.run import run as run_spec
from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.core.hardware import DEVICE_KINDS, HARDWARE, HardwareSpec
from repro.core.opmodels.calibration import measure_cpu_hardware
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.engine import MiniEngine, ServeRequest


def hardware_for(device) -> HardwareSpec:
    """The profile the simulator prices ``device`` with: the table entry
    for a chip's ``device_kind``, or a micro-benchmark of the host CPU."""
    if device.platform == "cpu":
        return measure_cpu_hardware()
    try:
        return DEVICE_KINDS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no hardware profile for {device.platform} device_kind "
            f"{device.device_kind!r}; known: {sorted(DEVICE_KINDS)} "
            f"(add it to repro.core.hardware.DEVICE_KINDS)") from None


def seeded_prompts(cfg: ModelConfig, n: int, lo: int, hi: int,
                   seed: int) -> List[np.ndarray]:
    """``n`` random-token prompts with lengths uniform in [lo, hi]."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi + 1, n)
    return [rng.integers(0, cfg.vocab_size, int(s)) for s in lens]


@dataclass
class Served:
    engine: MiniEngine
    requests: List[ServeRequest]     # the measured pass's requests
    measured: Dict[str, float]
    warm_s: float                    # first pass: compiles every step, serves


def serve(cfg: ModelConfig, prompts: Sequence[np.ndarray], output_len: int,
          *, max_slots: int, max_seq: int, seed: int = 0,
          dtype=jnp.bfloat16) -> Served:
    """Serve ``prompts`` twice: a warm pass that compiles every prefill
    bucket and the decode step, then the measured pass."""
    engine = MiniEngine(cfg, max_slots=max_slots, max_seq=max_seq,
                        seed=seed, dtype=dtype)
    t0 = time.perf_counter()
    engine.submit(list(prompts), output_len)
    engine.run()
    warm_s = time.perf_counter() - t0
    engine.step_log.clear()
    reqs = engine.submit(list(prompts), output_len)
    measured = engine.run()
    return Served(engine, reqs, measured, warm_s)


def predict(served: Served, hw: HardwareSpec, *, arch: str, smoke: bool,
            layers: Optional[int], seed: int = 0,
            out_dir: str = os.path.join("artifacts", "serve")) -> Report:
    """Simulate the served batch: every request arrives at t=0 with the
    measured pass's prompt and output lengths, on a single replica with
    the engine's slot count as its batch limit.  Steps are priced by the
    ``jit`` backend, which runs on the same device as the engine."""
    os.makedirs(out_dir, exist_ok=True)
    trace = os.path.join(out_dir, "served.trace.jsonl")
    with open(trace, "w") as f:
        for r in served.requests:
            f.write(json.dumps({"arrival": 0.0, "prompt_len": len(r.prompt),
                                "output_len": len(r.tokens)}) + "\n")
    topo = {"hardware": hw.name} if hw.name in HARDWARE else {}
    spec = SimSpec(
        name=f"serve-{arch}",
        model=ModelRef(arch, smoke=smoke, layers=layers),
        topology=TopologySpec(preset="colocated", memoize=False, **topo),
        workload=WorkloadSpec(n_requests=len(served.requests), trace=trace),
        policy=PolicySpec(batching={"name": "continuous",
                                    "max_num_seqs": served.engine.max_slots}),
        opmodel=OpModelSpec(backend="jit"),
        seed=seed)
    return run_spec(spec, hardware=hw)


def run(arch: str = "qwen2-7b", *, smoke: bool = True,
        layers: Optional[int] = None, n_requests: int = 4,
        prompt_min: int = 32, prompt_max: int = 32, output_len: int = 32,
        max_slots: int = 4, max_seq: int = 256,
        seed: int = 0) -> Dict[str, object]:
    hw = hardware_for(jax.devices()[0])
    cfg = get_config(arch, smoke=smoke, layers=layers)
    prompts = seeded_prompts(cfg, n_requests, prompt_min, prompt_max, seed)
    served = serve(cfg, prompts, output_len, max_slots=max_slots,
                   max_seq=max_seq, seed=seed)
    predicted = predict(served, hw, arch=arch, smoke=smoke, layers=layers,
                        seed=seed)
    return {"hardware": hw.name, "measured": served.measured,
            "predicted": predicted.summary, "warm_s": served.warm_s}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family widths (CPU-sized)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to this many layers")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-min", type=int, default=32)
    ap.add_argument("--prompt-max", type=int, default=32)
    ap.add_argument("--output-len", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    a = ap.parse_args()
    enable_compile_cache()
    out = run(a.arch, smoke=a.smoke, layers=a.layers, n_requests=a.requests,
              prompt_min=a.prompt_min, prompt_max=a.prompt_max,
              output_len=a.output_len, max_slots=a.slots,
              max_seq=a.max_seq)
    m, p = out["measured"], out["predicted"]
    print(f"hardware  : {out['hardware']}")
    print(f"measured  : {m['throughput_tok_s']:.1f} tok/s "
          f"(ttft {m['ttft_mean_s']*1e3:.1f} ms, "
          f"tpot {m['tpot_mean_s']*1e3:.2f} ms)")
    print(f"predicted : {p['throughput_tok_s']:.1f} tok/s "
          f"(ttft {p['ttft_p50_s']*1e3:.1f} ms, "
          f"tpot {p['tpot_p50_s']*1e3:.2f} ms)")
    err = abs(p["throughput_tok_s"] - m["throughput_tok_s"]) / m["throughput_tok_s"]
    print(f"relative error: {err:.1%}")


if __name__ == "__main__":
    main()
