"""One run of one benchmark cell on the accelerator JAX finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up draws the weights from the seed on the device, builds MiniEngine,
compiles every program the cell's prompts use, and (open loop) serves the
arrivals due before the window; then the window is measured for ``--seconds``
seconds.  After it, the engine is freed and the plain reference checks a
sample of what was served.  The last line of standard output is one JSON
object; ``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics from a profiler trace of the window.

Without an accelerator, or with fewer chips than the cell asks for, it
exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = ROOT / ".jax_cache"


class NoChip(Exception):
    pass


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def enable_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache/`` in the checkout (a fixed path, so the
    next run finds it).  Every program is cached, however small."""
    import jax
    path = os.environ.get(CACHE_ENV) or str(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Programs obtained (JAX times each, whether it compiles or reads it
    from the persistent cache) and programs read from that cache."""

    def __init__(self):
        import jax
        self.obtained = self.hits = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.obtained += 1

    def snapshot(self):
        return (self.obtained, self.hits)


def devices_for(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < chips:
        raise NoChip(f"{len(devs)} {devs[0].platform} device(s); the cell "
                     f"needs {chips} accelerator chip(s)")
    return devs


def peaks_for(device) -> Dict[str, float]:
    from bench.spec import BENCH_DIR, load_json
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if device.device_kind not in table:
        raise ValueError(f"no peaks for device_kind {device.device_kind!r} "
                         f"in bench/peaks.json")
    return table[device.device_kind]


@dataclass
class Run:
    """What a per-layer metric reads (``bench/metrics/<name>.py``)."""
    window: Any              # loop.Window, host clock
    spans: List[Any]         # engine_adapter.Span, indexed by id
    trace: Any               # trace.Trace, the trace's clock
    trace_window: tuple      # (open, close) on the trace's clock
    dims: Any                # shapes.Dims
    peaks: Dict[str, float]


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             control: bool = False):
    """Set up, measure and check one run; returns the result object.  With
    ``control``, also reads the float8 control's gap on the same sample."""
    from bench import correct, loop, spec, traffic
    from bench import trace as trace_mod
    cell = spec.load_cell(workload)
    mix = cell.traffic
    cache_dir = enable_cache()
    import jax
    counter = CompileCounter()
    t = time.perf_counter()
    devs = devices_for(cell.chips)
    split = {"backend": time.perf_counter() - t}
    peaks = peaks_for(devs[0]) if trace else {}

    from bench.engine_adapter import Adapter
    sched = traffic.schedule(mix, cell.config["vocab_size"], seed, seconds)
    adapter = Adapter(cell.config, seed)
    split["weights"], split["engine"] = adapter.weights_s, adapter.engine_s
    t = time.perf_counter()
    n_buckets = adapter.warm_up([len(r.prompt) for r in sched.requests])
    # set-up's garbage is collected now, and what survives it is never
    # scanned again, so no collection of it pauses the window
    gc.collect()
    gc.freeze()
    split["warm_up"] = time.perf_counter() - t

    cap = trace_mod.capture().__enter__() if trace else None
    at_open = {}

    def on_open():
        at_open["compiles"] = counter.snapshot()
        # a TraceAnnotation's span starts when it is made
        at_open["ann"] = jax.profiler.TraceAnnotation("window")
        at_open["ann"].__enter__()

    t_loop = time.perf_counter()
    w = loop.run(adapter, sched, seconds, on_open)
    at_open["ann"].__exit__(None, None, None)
    in_window = [b - a for a, b in zip(at_open["compiles"],
                                       counter.snapshot())]
    if cap is not None:
        cap.__exit__(None, None, None)
    split["arrivals_before_window"] = w.open - t_loop
    setup_s = w.open - T_START
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devs[:cell.chips])
    spans = adapter.spans
    dims = adapter.dims
    adapter.close()

    e2e = {"ttft_p95_ms": loop.p95(loop.ttft_ms(w)) if w.due_in_window()
           else None,
           "tpot_p95_ms": loop.p95(loop.tpot_ms(w)) if loop.tpot_ms(w)
           else None,
           "output_tok_s": loop.tokens_in_window(w) / w.seconds,
           "setup_s": setup_s}
    log(f"[setup] {setup_s:.3f} s: " + ", ".join(
        f"{k} {v:.3f} s" for k, v in split.items())
        + f"; {n_buckets} prefill bucket(s) warmed")
    log(f"[cache] {cache_dir}: {counter.hits} program(s) read from the "
        f"persistent cache, {counter.obtained - counter.hits} compiled; in "
        f"the window {in_window[0]} obtained ({in_window[1]} read)")
    late = sorted(w.lateness)
    log(f"[generator] {len(late)} requests queued, lateness p50 "
        f"{1e3 * late[len(late) // 2]:.3f} ms, max {1e3 * late[-1]:.3f} ms")
    for name, vals in (("ttft_p95_ms", loop.ttft_ms(w)),
                       ("tpot_p95_ms", loop.tpot_ms(w))):
        if vals:
            p = loop.p95(vals)
            log(f"[tail] {name}: {len(vals)} samples, "
                f"{sum(v > p for v in vals)} above the p95 {p:.4f}; "
                f"median {sorted(vals)[len(vals) // 2]:.4f}")
    longest = {k: max(((s.t1 - s.t0, s.t0 - w.open) for s in spans
                       if s.kind == k and w.open <= s.t0 <= w.close),
                      default=(0.0, 0.0))
               for k in ("prefill", "decode")}
    log(f"[window] {w.seconds:.3f} s, {loop.tokens_in_window(w)} tokens, "
        f"{sum(r.win_first is not None for r in w.requests)} requests "
        f"emitted tokens; longest call: " + ", ".join(
            f"{k} {1e3 * d:.1f} ms at {at:.3f} s"
            for k, (d, at) in longest.items()))

    result: Dict[str, Any] = {}
    if trace:
        tr = trace_mod.load(cap.path)
        cap.cleanup()
        tw = tr.window
        run = Run(w, spans, tr, tw, dims, peaks)
        values = {m.name: spec.metric_reader(m.name)(run)
                  for m in cell.per_layer}
        metrics = {m.name: {"value": values[m.name], "unit": m.unit}
                   for m in cell.per_layer if values[m.name] is not None}
        busy = trace_mod.busy_s(tr, *tw)
        result["breakdown"] = trace_mod.breakdown(tr, *tw)
    else:
        metrics = {m.name: {"value": e2e[m.name], "unit": m.unit}
                   for m in cell.end_to_end if e2e[m.name] is not None}
    for m in (cell.per_layer if trace else cell.end_to_end):
        if m.name not in metrics:
            log(f"[metrics] {m.name}: nothing to read in this run")

    chk = mix["check"]
    s = correct.sample(w, seed, chk["served_tokens"], chk["max_requests"])
    t = time.perf_counter()
    reading = correct.check(cell.config, dims, seed, s, control)
    log(f"[check] reference over {s.requests} requests, {s.tokens} served "
        f"tokens, {time.perf_counter() - t:.3f} s; mean gap "
        f"{reading.mean_logit_gap:.6f}; widest {reading.worst}"
        + (f"; float8 control's mean gap {reading.control.mean_logit_gap:.6f}"
           f", widest {reading.control.max_logit_gap:.6f}"
           if control else ""))
    checks = correct.verdict(reading, chk)
    ok = all(c["value"] <= c["limit"] for c in checks.values())

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(memory_peak)}
    if trace:
        device.update(busy_s=busy, window_s=tw[1] - tw[0])
    attempted = (len(w.due_in_window()) if w.loop == "open" else
                 sum(r.win_first is not None for r in w.requests))
    out = {"correct": ok, "attempted": attempted, "failed": 0,
           "metrics": metrics, "device": device, **result, "checks": checks}
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}")
    return out, reading


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out, _ = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
