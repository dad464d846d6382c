"""Bring-up check of the device path on TPU v5e, through the entry points a
user calls.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: EP=4 MoE layer only

One chip runs four phases in order, in this one process (a chip belongs to
one process at a time):

1. device     -- the attached devices and the hardware profile they map to;
2. kernels    -- ``PallasOracle`` times the three main-path Pallas kernels,
                 and each is checked against ``kernels/ref.py`` at
                 qwen2-7b / mixtral-8x7b widths;
3. serving    -- MiniEngine serves qwen2-7b at its published widths in bf16
                 (depth cut to ``LAYERS``) through ``launch/serve.py``, and
                 two requests are checked against a reference greedy decode;
4. simulator  -- ``run(spec)`` predicts the same batch on the chip's profile
                 with ``opmodel.backend: jit``, whose pricing runs on the chip.

``--chips 4`` runs one mixtral-8x7b MoE layer at full width with EP=4 over
a ("data", "model") = (1, 4) mesh, in both dispatch modes, against the same
layer on one chip.

Weights and inputs are random, drawn from ``SEED``.  A failed phase raises,
so the script exits non-zero.  The last line of a passing run is one JSON
object: {"ok": true, "device": {"platform", "kind", "count"}}.  The numbers
printed are single bring-up runs, not benchmark measurements.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from collections import Counter

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

SEED = 0
# qwen2-7b depth cut: the deepest whole layer count whose prefill and decode
# programs, compiled for v5e, leave at least 1.5 GB of its 15.75 GiB free at
# 8 slots x max_seq 2048 in bf16 (24 of 28 leaves 1.80 GiB; 25 leaves 1.30).
LAYERS = 24
MODEL = dict(smoke=False, layers=LAYERS)
SLOTS, MAX_SEQ = 8, 2048
N_REQUESTS, PROMPT_MIN, PROMPT_MAX, NEW_TOKENS = 16, 128, 1024, 64
# Engine vs reference logits, as a fraction of the row's largest |logit|.
# Both run bf16 through 24 layers, and the engine prefills a padded bucket
# where the reference prefills the exact length, so their roundings differ
# (first-step logits by ~0.1 of a ~6 maximum on the chip); a faulty engine
# differs by the logits' whole scale.  The first-step logits must agree
# within it, and a greedy token may differ from the reference's only where
# the reference's logit for it lies within it of the row's max (a bf16 tie).
LOGIT_RTOL = 0.05
GiB = 2 ** 30


def tol(dtype):
    """The kernel tolerances of ``tests/test_kernels.py``."""
    import jax.numpy as jnp
    return (dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16
            else dict(atol=2e-5, rtol=2e-5))


def check_close(name, got, want, **tolerance):
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        raise AssertionError(f"{name}: shape {got.shape} vs {want.shape}, "
                             f"finite={np.all(np.isfinite(got))}")
    err = float(np.max(np.abs(got - want)))
    print(f"  {name}: max|err| {err:.3e} (atol {tolerance['atol']}, "
          f"rtol {tolerance['rtol']})")
    np.testing.assert_allclose(got, want, **tolerance)


# --------------------------------------------------------------- device --
def device_phase():
    import jax
    from repro.launch.serve import hardware_for
    devices = jax.devices()
    dev = devices[0]
    print(f"[device] jax.devices(): {devices}")
    print(f"[device] platform {dev.platform}, device_kind "
          f"{dev.device_kind!r}, count {len(devices)}")
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX's platform is "
                         f"{dev.platform!r})")
    hw = hardware_for(dev)
    print(f"[device] profile {hw.name}: peak {hw.peak_flops:.4g} FLOP/s "
          f"bf16, HBM {hw.hbm_bw:.4g} B/s, {hw.hbm_capacity:.4g} B")
    return dev, hw


# -------------------------------------------------------------- kernels --
def kernel_phase(hw):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.calib import PallasOracle
    from repro.kernels import ops, ref

    orc = PallasOracle(hw, reps=3)
    counts = [1024, 768, 640, 512, 448, 384, 192, 128]   # 2048 tok x top-2
    times = {
        "flash_attention (1x2048, 28/4 heads, hd 128, f32)":
            orc.attention_prefill([2048], [2048], 28, 4, 128),
        "decode_attention (8 x ctx 4096, 28/4 heads, hd 128, f32)":
            orc.attention_decode([4096] * 8, 28, 4, 128),
        "grouped_gemm (8 experts, 4096->14336, f32)":
            orc.grouped_gemm(counts, 4096, 14336),
    }
    for name, t in times.items():
        if not (t > 0 and np.isfinite(t)):
            raise AssertionError(f"PallasOracle {name}: time {t}")
        print(f"[kernels] PallasOracle {name}: {t * 1e3:.3f} ms")

    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 32))

    def arr(*shape, dtype, scale=0.5):   # as test_kernels', on the device
        return (scale * jax.random.normal(next(keys), shape)).astype(dtype)

    def ints(lo, hi, n):         # n ints in [lo, hi)
        return jax.random.randint(next(keys), (n,), lo, hi, jnp.int32)

    for dtype in (jnp.bfloat16, jnp.float32):
        dn = jnp.dtype(dtype).name
        q, k, v = (arr(1, 2048, 28, 128, dtype=dtype),
                   arr(1, 2048, 4, 128, dtype=dtype),
                   arr(1, 2048, 4, 128, dtype=dtype))
        check_close(f"flash_attention {dn}",
                    ops.flash_attention(q, k, v, causal=True),
                    ref.flash_attention_ref(q, k, v, causal=True),
                    **tol(dtype))
        q, k, v = (arr(8, 28, 128, dtype=dtype),
                   arr(8, 4096, 4, 128, dtype=dtype),
                   arr(8, 4096, 4, 128, dtype=dtype))
        lens = ints(1, 4097, 8)
        check_close(f"decode_attention {dn}",
                    ops.decode_attention(q, k, v, lens),
                    ref.decode_attention_ref(q, k, v, lens), **tol(dtype))
        # expert weights fan-in scaled, as models/common.py draws them: at
        # a 4096-long contraction, float32 accumulation-order differences
        # then stay within the float32 tolerance near zero outputs
        x = arr(8, 1024, 4096, dtype=dtype)
        w = arr(8, 4096, 14336, dtype=dtype, scale=4096 ** -0.5)
        gs = ints(0, 1025, 8)
        check_close(f"grouped_gemm {dn}", ops.grouped_gemm(x, w, gs),
                    ref.grouped_gemm_ref(x, w, gs), **tol(dtype))
        del q, k, v, x, w


# -------------------------------------------------------------- serving --
def reference_greedy(model, params, prompt, n_new: int, max_seq: int):
    """The reference of ``tests/test_serving.py``: unpadded prefill, then a
    batch-1 decode loop.  Returns the tokens and each step's logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    prefill = jax.jit(lambda p, t: model.prefill(
        p, {"tokens": t}, cache_len=max_seq, all_logits=True))
    decode = jax.jit(model.decode)
    logits, cache = prefill(params, jnp.asarray(prompt, jnp.int32)[None])
    rows = [np.asarray(logits[0, len(prompt) - 1], np.float32)]
    out = [int(np.argmax(rows[-1]))]
    pos = len(prompt)
    for _ in range(n_new - 1):
        logits, cache = decode(params, cache,
                               jnp.asarray([[out[-1]]], jnp.int32),
                               jnp.int32(pos))
        rows.append(np.asarray(logits[0, 0], np.float32))
        out.append(int(np.argmax(rows[-1])))
        pos += 1
    return out, rows


def engine_first_logits(engine, prompt):
    """The first-step logits along the engine's own prefill path: the
    prompt padded to its length bucket, logits taken at the true end."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.serving.engine import _bucket
    S = len(prompt)
    toks = np.zeros((1, min(_bucket(S), engine.max_seq)), np.int32)
    toks[0, :S] = prompt
    prefill = jax.jit(lambda p, t, last: engine.model.prefill(
        p, {"tokens": t}, cache_len=engine.max_seq, last=last))
    logits, _ = prefill(engine.params, jnp.asarray(toks),
                        jnp.asarray([S - 1], jnp.int32))
    return np.asarray(logits[0, 0], np.float32)


def check_against_reference(engine, req):
    import numpy as np
    want, rows = reference_greedy(engine.model, engine.params, req.prompt,
                                  len(req.tokens), engine.max_seq)
    first = engine_first_logits(engine, req.prompt)
    d_first = float(np.max(np.abs(first - rows[0])))
    tol = LOGIT_RTOL * float(np.max(np.abs(rows[0])))
    print(f"  request {req.rid} (prompt {len(req.prompt)}): first-step "
          f"logits max|engine - reference| {d_first:.4f}, tolerance "
          f"{tol:.4f} ({LOGIT_RTOL} x max|logit|)")
    if d_first > tol:
        raise AssertionError(f"request {req.rid}: first-step logits differ "
                             f"by {d_first} > {tol}")
    for i, (got, ref_tok) in enumerate(zip(req.tokens, want)):
        if got != ref_tok:
            gap = float(rows[i].max() - rows[i][got])
            tol = LOGIT_RTOL * float(np.max(np.abs(rows[i])))
            print(f"  request {req.rid}: tokens equal for {i} of "
                  f"{len(want)} steps; at step {i} the engine's token "
                  f"{got} lies {gap:.4f} below the reference max "
                  f"(tie tolerance {tol:.4f})")
            if gap > tol:
                raise AssertionError(
                    f"request {req.rid}: step {i} token {got} vs reference "
                    f"{ref_tok}, logit gap {gap} > {tol}")
            return
    print(f"  request {req.rid}: all {len(want)} tokens equal the "
          f"reference greedy decode")


def serving_phase(dev, cache_events):
    import numpy as np
    from repro.configs import get_config
    from repro.launch.serve import seeded_prompts, serve

    cfg = get_config("qwen2-7b", **MODEL)
    print(f"[serving] {cfg.name} bf16, d_model {cfg.d_model}, heads "
          f"{cfg.num_heads}/{cfg.num_kv_heads}, head_dim "
          f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}; depth cut to {cfg.num_layers} of "
          f"{get_config('qwen2-7b').num_layers} layers; {SLOTS} slots x "
          f"max_seq {MAX_SEQ}")
    prompts = seeded_prompts(cfg, N_REQUESTS, PROMPT_MIN, PROMPT_MAX, SEED)
    before = Counter(cache_events)
    served = serve(cfg, prompts, NEW_TOKENS, max_slots=SLOTS,
                   max_seq=MAX_SEQ, seed=SEED)
    m = served.measured
    if m["n_requests"] != N_REQUESTS or any(
            len(r.tokens) != NEW_TOKENS for r in served.requests):
        raise AssertionError(f"served {m['n_requests']} requests with "
                             f"{[len(r.tokens) for r in served.requests]} "
                             f"tokens")
    for key in ("throughput_tok_s", "ttft_mean_s", "tpot_mean_s"):
        if not (m[key] > 0 and np.isfinite(m[key])):
            raise AssertionError(f"measured {key} = {m[key]}")
    stats = dev.memory_stats() or {}
    cache = Counter(cache_events) - before
    print(f"[serving] {N_REQUESTS} requests, prompts "
          f"{min(map(len, prompts))}-{max(map(len, prompts))} tokens, "
          f"{NEW_TOKENS} new tokens each")
    print(f"[serving] TTFT mean {m['ttft_mean_s'] * 1e3:.2f} ms, TPOT mean "
          f"{m['tpot_mean_s'] * 1e3:.3f} ms, {m['throughput_tok_s']:.1f} "
          f"tok/s, measured pass {m['duration_s']:.3f} s")
    steps = served.engine.step_log
    dec = [st["dur"] for st in steps if st["kind"] == "decode"]
    pre = [st["dur"] for st in steps if st["kind"] == "prefill"]
    print(f"[serving] host-clock step medians: decode ({SLOTS} slots) "
          f"{np.median(dec) * 1e3:.3f} ms over {len(dec)} steps, prefill "
          f"{np.median(pre) * 1e3:.3f} ms over {len(pre)} prompts")
    print(f"[serving] warm pass (compiles every step, then serves) "
          f"{served.warm_s:.2f} s; compile estimate (warm - measured) "
          f"{served.warm_s - m['duration_s']:.2f} s; persistent cache "
          f"hits {cache['hits']}, writes {cache['writes']}")
    print(f"[serving] device memory: peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 0) / GiB:.3f} GiB, bytes_in_use "
          f"{stats.get('bytes_in_use', 0) / GiB:.3f} GiB, bytes_limit "
          f"{stats.get('bytes_limit', 0) / GiB:.3f} GiB")
    print("[serving] reference greedy check:")
    for req in served.requests[:2]:
        check_against_reference(served.engine, req)
    return served


# ------------------------------------------------------------ simulator --
def simulator_phase(served, hw):
    import jax
    import numpy as np
    from repro.core.opmodels.batch import _fused_kernel
    from repro.launch.serve import predict

    rep = predict(served, hw, arch="qwen2-7b", seed=SEED,
                  out_dir=os.path.join("artifacts", "chip_smoke"), **MODEL)
    p, m = rep.summary, served.measured
    if not rep.all_complete:
        raise AssertionError(f"simulation incomplete: {rep.conservation}")
    # the jit backend's fused roofline kernel runs on JAX's default device
    compiles = _fused_kernel(hw.peak_flops, hw.hbm_bw)._cache_size()
    if compiles < 1:
        raise AssertionError("the fused roofline kernel never ran")
    print(f"[simulator] run(spec) on {hw.name}, opmodel.backend jit: fused "
          f"roofline kernel compiled {compiles}x for "
          f"{jax.default_backend()}")
    for key, mk in (("ttft_mean_s", "ttft_mean_s"),
                    ("tpot_mean_s", "tpot_mean_s"),
                    ("throughput_tok_s", "throughput_tok_s")):
        if not (p[key] > 0 and np.isfinite(p[key])):
            raise AssertionError(f"predicted {key} = {p[key]}")
        print(f"[simulator] {key:18s} predicted {p[key]:.6g}  measured "
              f"{m[mk]:.6g}")


# ------------------------------------------------------------ four chips --
def moe_phase(devices, *, batch: int = 4, seq: int = 512, smoke=False):
    """mixtral-8x7b MoE layer, EP over ``devices`` vs the same layer on
    ``devices[0]``, in both dispatch modes; dropless capacity."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.models.common import AxisRules, init_tree
    from repro.models.moe import moe_apply, moe_pds

    base = get_config("mixtral-8x7b", smoke=smoke)
    E = base.moe.num_experts
    # capacity E * A / E = A: every expert can take every assignment
    cfg = dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, capacity_factor_eval=float(E)))
    dtype = jnp.bfloat16
    params = init_tree(jax.random.PRNGKey(SEED), moe_pds(cfg), dtype)
    x = jax.random.normal(jax.random.PRNGKey(SEED + 1),
                          (batch, seq, cfg.d_model), dtype)
    one = jax.jit(lambda p, x: moe_apply(cfg, p, x, AxisRules(None),
                                         train=False))
    want, aux = one(params, x)
    print(f"[moe] mixtral-8x7b layer: {E} experts, d {cfg.d_model}, ff "
          f"{cfg.moe.expert_d_ff}, top-{cfg.moe.top_k}, {batch}x{seq} "
          f"tokens, bf16; one-chip drop fraction "
          f"{float(aux['moe_drop_frac']):.3g}")
    mesh = Mesh(np.asarray(devices).reshape(1, len(devices)),
                ("data", "model"))
    host = jax.device_get(params)
    del params
    for mode in ("psum", "a2a"):
        ax = AxisRules(mesh, {"moe_dispatch": mode})
        shard = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), ax.spec_tree(moe_pds(cfg)))
        p_ep = jax.device_put(host, shard)
        ep = jax.jit(lambda p, x: moe_apply(cfg, p, x, ax, train=False))
        t0 = time.perf_counter()
        got, aux = ep(p_ep, jax.device_put(x, NamedSharding(mesh, P())))
        got.block_until_ready()
        dt = time.perf_counter() - t0
        w_in = p_ep["w_in"]
        print(f"[moe] EP={len(devices)} {mode}: first call {dt:.2f} s, "
              f"drop fraction {float(aux['moe_drop_frac']):.3g}; w_in "
              f"{w_in.shape} shards: "
              + ", ".join(f"{s.device.id}:{s.data.shape}"
                          for s in w_in.addressable_shards))
        in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0) / GiB
                  for d in devices]
        print("[moe] per-device bytes_in_use (GiB): "
              + ", ".join(f"{d.id}:{b:.3f}" for d, b in zip(devices, in_use)))
        if float(aux["moe_drop_frac"]) != 0.0:
            raise AssertionError(f"{mode}: tokens dropped at dropless "
                                 f"capacity")
        check_close(f"EP={len(devices)} {mode} vs one chip", got, want,
                    **tol(dtype))
        del p_ep


# ----------------------------------------------------------------- main --
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the EP=4 MoE comparison")
    args = ap.parse_args()
    cache_dir = enable_compile_cache()
    import jax
    cache_events: Counter = Counter()
    names = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "writes"}

    def on_event(event: str, **_) -> None:
        if event in names:
            cache_events[names[event]] += 1

    jax.monitoring.register_event_listener(on_event)
    print(f"[cache] persistent compilation cache: {cache_dir}")

    t0 = time.perf_counter()
    dev, hw = device_phase()
    if args.chips == 4:
        devices = jax.devices()
        if len(devices) < 4:
            raise SystemExit(f"chip_smoke --chips 4: {len(devices)} "
                             f"device(s) attached")
        moe_phase(devices[:4])
    else:
        t = time.perf_counter()
        kernel_phase(hw)
        print(f"[kernels] phase {time.perf_counter() - t:.1f} s")
        t = time.perf_counter()
        served = serving_phase(dev, cache_events)
        print(f"[serving] phase {time.perf_counter() - t:.1f} s")
        t = time.perf_counter()
        simulator_phase(served, hw)
        print(f"[simulator] phase {time.perf_counter() - t:.1f} s")
    print(f"[done] {time.perf_counter() - t0:.1f} s; persistent cache hits "
          f"{cache_events['hits']}, writes {cache_events['writes']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
