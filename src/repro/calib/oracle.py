"""Operator-latency oracles — the ground truth calibration fits against.

An ``Oracle`` answers "how long does this operator take on this hardware
for this exact heterogeneous batch?" in seconds.  Three backends, one per
rung of the fidelity ladder:

``pallas``     wall-clock timing of the real Pallas kernels in
               ``kernels/ops.py`` (compiled on TPU; interpret mode on
               CPU — functional but slow, so shape limits shrink).
``kernelsim``  the ``VirtualKernels`` tile-level simulator: deterministic,
               fast, models wave quantization and head/tile parallelism.
``hlo``        the HLO-cost proxy: jit-lower the jnp reference ops,
               run ``launch/hlo_cost.analyze`` on the compiled module, and
               price flops/bytes on the target hardware roofline.

``resolve_oracle`` picks automatically by environment ("auto"): the real
kernels when JAX's backend is a TPU, the virtual kernels otherwise — so
`python -m repro calibrate` does the right thing on both a laptop and a
TPU VM.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from repro.core.hardware import HardwareSpec
from repro.core.opmodels.kernelsim import VirtualKernels


class Oracle:
    """Protocol: per-operator latency (seconds) for one heterogeneous batch.

    ``limits()`` advertises the largest shapes the backend can measure in
    reasonable time — the grid sampler clamps to it, so a slow backend
    (interpreted Pallas on CPU) still calibrates, just on a smaller domain.
    """

    name = "oracle"

    def attention_prefill(self, q_lens: Sequence[int],
                          kv_lens: Sequence[int], n_heads: int,
                          n_kv_heads: int, head_dim: int, *,
                          causal: bool = True, window: int = 0) -> float:
        raise NotImplementedError

    def attention_decode(self, context_lens: Sequence[int], n_heads: int,
                         n_kv_heads: int, head_dim: int, *,
                         window: int = 0) -> float:
        raise NotImplementedError

    def grouped_gemm(self, tokens_per_expert: Sequence[int], d_in: int,
                     d_out: int) -> float:
        raise NotImplementedError

    def limits(self) -> Dict[str, int]:
        return {"max_len": 8192, "max_batch": 128, "max_tokens": 16384}

    # fit_attention_model-compatible entry point: decode batches are the
    # all-q==1 case, matching how the predictor prices decode attention
    def attention(self, q_lens, kv_lens, n_heads, n_kv_heads, head_dim,
                  causal=True, window=0) -> float:
        if any(int(q) > 1 for q in q_lens):
            return self.attention_prefill(q_lens, kv_lens, n_heads,
                                          n_kv_heads, head_dim,
                                          causal=causal, window=window)
        return self.attention_decode(kv_lens, n_heads, n_kv_heads,
                                     head_dim, window=window)


class KernelSimOracle(Oracle):
    """VirtualKernels tile-level simulator as ground truth (default on CPU)."""

    name = "kernelsim"

    def __init__(self, hw: HardwareSpec):
        self.hw = hw
        self.kernels = VirtualKernels(hw)

    def attention_prefill(self, q_lens, kv_lens, n_heads, n_kv_heads,
                          head_dim, *, causal=True, window=0) -> float:
        return self.kernels.attention_prefill(q_lens, kv_lens, n_heads,
                                              n_kv_heads, head_dim,
                                              causal=causal, window=window)

    def attention_decode(self, context_lens, n_heads, n_kv_heads, head_dim,
                         *, window=0) -> float:
        return self.kernels.attention_decode(context_lens, n_heads,
                                             n_kv_heads, head_dim,
                                             window=window)

    def grouped_gemm(self, tokens_per_expert, d_in, d_out) -> float:
        return self.kernels.grouped_gemm(tokens_per_expert, d_in, d_out)


class PallasOracle(Oracle):
    """Wall-clock timing of the real Pallas kernels (``kernels/ops.py``).

    On an accelerator this measures the actual kernels; on CPU the kernels
    run in Pallas interpret mode, which is orders of magnitude slower than
    real silicon — so per-shape timings are cached (bucketed geometrically
    by length) and ``limits()`` shrinks the sampling domain to keep a
    calibration run tractable.  The cache is sound because kernel latency
    is a pure function of the (padded) shape.
    """

    name = "pallas"

    def __init__(self, hw: HardwareSpec, reps: int = 2, bucket: float = 1.25):
        self.hw = hw
        self.reps = reps
        self.bucket = bucket
        self._cache: Dict[tuple, float] = {}
        import jax  # hard dep of the kernels; fail loud at construction
        self._jax = jax
        self._on_accel = jax.default_backend() == "tpu"

    def limits(self) -> Dict[str, int]:
        if self._on_accel:
            return {"max_len": 8192, "max_batch": 64, "max_tokens": 8192}
        return {"max_len": 160, "max_batch": 4, "max_tokens": 512}

    def _round(self, n: int) -> int:
        # geometric bucketing: pads lengths up so the shape cache hits
        if n <= 16:
            return 16
        b = 16
        while b < n:
            b = max(b + 16, int(b * self.bucket) // 16 * 16)
        return b

    def _time(self, fn: Callable, *args) -> float:
        out = fn(*args)
        self._jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(self.reps):
            out = fn(*args)
            self._jax.block_until_ready(out)
        return (time.perf_counter() - t0) / self.reps

    def attention_prefill(self, q_lens, kv_lens, n_heads, n_kv_heads,
                          head_dim, *, causal=True, window=0) -> float:
        import jax.numpy as jnp
        from repro.kernels import ops
        total = 0.0
        for q_len, kv_len in zip(q_lens, kv_lens):
            s, t = self._round(int(q_len)), self._round(int(kv_len))
            key = ("prefill", s, t, n_heads, n_kv_heads, head_dim,
                   causal, window)
            if key not in self._cache:
                q = jnp.ones((1, s, n_heads, head_dim), jnp.float32)
                k = jnp.ones((1, t, n_kv_heads, head_dim), jnp.float32)
                bq = bk = min(128, max(16, s))
                self._cache[key] = self._time(
                    lambda q, k: ops.flash_attention(
                        q, k, k, causal=causal, window=window, bq=bq, bk=bk),
                    q, k)
            total += self._cache[key]
        return total

    def attention_decode(self, context_lens, n_heads, n_kv_heads, head_dim,
                         *, window=0) -> float:
        import jax.numpy as jnp
        from repro.kernels import ops
        # one fused decode kernel over the whole batch: pad contexts to the
        # bucketed max and pass true lengths, exactly how the engine runs it
        b = len(context_lens)
        t = self._round(max(int(x) for x in context_lens))
        key = ("decode", b, t, n_heads, n_kv_heads, head_dim, window)
        if key not in self._cache:
            q = jnp.ones((b, n_heads, head_dim), jnp.float32)
            k = jnp.ones((b, t, n_kv_heads, head_dim), jnp.float32)
            lengths = jnp.asarray([min(int(x), t) for x in context_lens],
                                  jnp.int32)
            self._cache[key] = self._time(
                lambda q, k, lengths: ops.decode_attention(
                    q, k, k, lengths, bk=min(256, t)),
                q, k, lengths)
        return self._cache[key]

    def grouped_gemm(self, tokens_per_expert, d_in, d_out) -> float:
        import jax.numpy as jnp
        from repro.kernels import ops
        e = len(tokens_per_expert)
        cap = self._round(max(1, max(int(x) for x in tokens_per_expert)))
        key = ("grouped", e, cap, d_in, d_out)
        if key not in self._cache:
            x = jnp.ones((e, cap, d_in), jnp.float32)
            w = jnp.ones((e, d_in, d_out), jnp.float32)
            sizes = jnp.asarray([min(int(t), cap)
                                 for t in tokens_per_expert], jnp.int32)
            bm = min(128, max(16, cap))
            self._cache[key] = self._time(
                lambda x, w, sizes: ops.grouped_gemm(
                    x, w, sizes, bm=bm, bn=min(128, d_out),
                    bkk=min(512, d_in)),
                x, w, sizes)
        return self._cache[key]


class HLOCostOracle(Oracle):
    """HLO-cost proxy: lower the jnp reference ops with ``jax.jit``, parse
    the compiled module with ``launch/hlo_cost.analyze``, and price the
    flop/byte totals on the target hardware's roofline.  Compilation is
    the expensive part, so shapes are bucketed and analyses cached.
    """

    name = "hlo"

    def __init__(self, hw: HardwareSpec, bucket: float = 1.25):
        self.hw = hw
        self.bucket = bucket
        self._cache: Dict[tuple, float] = {}
        import jax
        self._jax = jax

    def limits(self) -> Dict[str, int]:
        return {"max_len": 2048, "max_batch": 16, "max_tokens": 4096}

    def _round(self, n: int) -> int:
        if n <= 16:
            return 16
        b = 16
        while b < n:
            b = max(b + 16, int(b * self.bucket) // 16 * 16)
        return b

    def _price(self, fn: Callable, *args) -> float:
        from repro.launch import hlo_cost
        text = self._jax.jit(fn).lower(*args).compile().as_text()
        costs = hlo_cost.analyze(text)
        return max(costs["flops"] / self.hw.peak_flops,
                   costs["bytes"] / self.hw.hbm_bw) + self.hw.op_overhead

    def attention_prefill(self, q_lens, kv_lens, n_heads, n_kv_heads,
                          head_dim, *, causal=True, window=0) -> float:
        import jax.numpy as jnp
        from repro.kernels import ref
        total = 0.0
        for q_len, kv_len in zip(q_lens, kv_lens):
            s, t = self._round(int(q_len)), self._round(int(kv_len))
            key = ("prefill", s, t, n_heads, n_kv_heads, head_dim,
                   causal, window)
            if key not in self._cache:
                q = self._jax.ShapeDtypeStruct((1, s, n_heads, head_dim),
                                               jnp.float32)
                k = self._jax.ShapeDtypeStruct((1, t, n_kv_heads, head_dim),
                                               jnp.float32)
                self._cache[key] = self._price(
                    lambda q, k, v: ref.flash_attention_ref(
                        q, k, v, causal=causal, window=window), q, k, k)
            total += self._cache[key]
        return total

    def attention_decode(self, context_lens, n_heads, n_kv_heads, head_dim,
                         *, window=0) -> float:
        import jax.numpy as jnp
        from repro.kernels import ref
        b = self._round(len(context_lens))
        t = self._round(max(int(x) for x in context_lens))
        key = ("decode", b, t, n_heads, n_kv_heads, head_dim, window)
        if key not in self._cache:
            q = self._jax.ShapeDtypeStruct((b, n_heads, head_dim),
                                           jnp.float32)
            k = self._jax.ShapeDtypeStruct((b, t, n_kv_heads, head_dim),
                                           jnp.float32)
            lengths = self._jax.ShapeDtypeStruct((b,), jnp.int32)
            self._cache[key] = self._price(ref.decode_attention_ref,
                                           q, k, k, lengths)
        return self._cache[key]

    def grouped_gemm(self, tokens_per_expert, d_in, d_out) -> float:
        import jax.numpy as jnp
        from repro.kernels import ref
        e = len(tokens_per_expert)
        cap = self._round(max(1, max(int(x) for x in tokens_per_expert)))
        key = ("grouped", e, cap, d_in, d_out)
        if key not in self._cache:
            x = self._jax.ShapeDtypeStruct((e, cap, d_in), jnp.float32)
            w = self._jax.ShapeDtypeStruct((e, d_in, d_out), jnp.float32)
            sizes = self._jax.ShapeDtypeStruct((e,), jnp.int32)
            self._cache[key] = self._price(ref.grouped_gemm_ref, x, w, sizes)
        return self._cache[key]


ORACLES: Dict[str, type] = {
    "kernelsim": KernelSimOracle,
    "pallas": PallasOracle,
    "hlo": HLOCostOracle,
}


def default_oracle_name() -> str:
    """Real kernels on a TPU, the virtual-kernel sim elsewhere."""
    import jax
    return "pallas" if jax.default_backend() == "tpu" else "kernelsim"


def resolve_oracle(spec, hw: HardwareSpec) -> Oracle:
    """Oracle instance / name / {"name": ..., **kwargs} / None ("auto")."""
    if isinstance(spec, Oracle):
        return spec
    if spec is None or spec == "auto":
        spec = default_oracle_name()
    if isinstance(spec, str):
        name, kwargs = spec, {}
    else:
        kwargs = dict(spec)
        name = kwargs.pop("name", None)
    if name not in ORACLES:
        raise KeyError(f"unknown oracle {name!r}; available: "
                       f"{sorted(ORACLES)} (or 'auto')")
    return ORACLES[name](hw, **kwargs)
