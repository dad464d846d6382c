"""Device: the share of the traced window, in %, in which no operation ran
on the device (one minus the union of the operations' intervals over the
window).  Moves ``output_tok_s`` in the backlog cell."""
from bench import trace


def read(run):
    t0, t1 = run.trace_window
    return 100.0 * (1.0 - trace.busy_s(run.trace, t0, t1) / (t1 - t0))
