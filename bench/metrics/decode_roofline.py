"""Roofline: the decode steps' share, in %, of their roofline: for each
step the least time the chip could take for the work it needs
(``bench/shapes.py``: active slots, live contexts, experts reached), the
larger of operations over peak FLOP/s and bytes over peak bytes/s, summed
and divided by the decode program's device time.  Moves
``tpot_p95_ms``."""
from bench import shapes, trace


def read(run):
    t = trace.program_time(run.trace, *run.trace_window)
    need = dev = 0.0
    for s in run.spans:
        if s.kind == "decode" and s.id in t:
            w = shapes.decode(run.dims, s.info["contexts"])
            need += max(w["flops"] / run.peaks["flops_per_s"],
                        w["bytes"] / run.peaks["hbm_bytes_per_s"])
            dev += t[s.id]
    return 100.0 * need / dev if dev else None
