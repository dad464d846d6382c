"""Roofline: the prefill programs' model FLOP utilisation, in %: operations
the window's prefills need (``bench/shapes.py``: real prompt tokens, top-k
experts) over their device time times the chip's peak FLOP/s.  Moves
``ttft_p95_ms``."""
from bench import shapes, trace


def read(run):
    t = trace.program_time(run.trace, *run.trace_window)
    flops = dev = 0.0
    for s in run.spans:
        if s.kind == "prefill" and s.id in t:
            flops += shapes.prefill(run.dims, s.info["tokens"])["flops"]
            dev += t[s.id]
    return 100.0 * flops / (dev * run.peaks["flops_per_s"]) if dev else None
