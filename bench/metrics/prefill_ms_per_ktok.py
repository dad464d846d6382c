"""Model step: device milliseconds of the prefill programs per 1000 real
prompt tokens prefilled in the window (profiler trace, programs matched to
the adapter's prefill spans; bucket padding is not counted as tokens).
Moves ``ttft_p95_ms``."""
from bench import trace


def read(run):
    t = trace.program_time(run.trace, *run.trace_window)
    pre = [s for s in run.spans if s.kind == "prefill" and s.id in t]
    tokens = sum(s.info["tokens"] for s in pre)
    return 1e6 * sum(t[s.id] for s in pre) / tokens if tokens else None
