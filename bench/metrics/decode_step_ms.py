"""Model step: device milliseconds of the decode program per decode step
in the window (profiler trace, programs matched to the adapter's decode
spans).  Moves ``tpot_p95_ms``."""
from bench import trace


def read(run):
    t = trace.program_time(run.trace, *run.trace_window)
    steps = [t[s.id] for s in run.spans if s.kind == "decode" and s.id in t]
    return 1e3 * sum(steps) / len(steps) if steps else None
