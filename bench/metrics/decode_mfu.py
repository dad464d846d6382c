"""Device: the decode program's model FLOP utilisation, in %: operations
the decode steps need (``bench/shapes.py``) over their device time times
the chip's peak FLOP/s.  Taken over the whole step program, so no rename of
a kernel inside it removes it.  Moves ``tpot_p95_ms``."""
from bench import shapes, trace


def read(run):
    t = trace.program_time(run.trace, *run.trace_window)
    flops = dev = 0.0
    for s in run.spans:
        if s.kind == "decode" and s.id in t:
            flops += shapes.decode(run.dims, s.info["contexts"])["flops"]
            dev += t[s.id]
    return 100.0 * flops / (dev * run.peaks["flops_per_s"]) if dev else None
