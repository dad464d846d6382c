"""Scheduler: the device's idle time before each decode step, in ms: for
each decode program (the module named ``engine_decode``) that starts in
the traced window, its start minus the latest end among the device
operations that began before it; the median over the window's steps, so
that a rare freeze of the host does not move it.  It is the host's share
of the step cycle (uploading inputs, dispatching, reading the ids back,
updating slots) that the device waits through.  A program whose decode
step has no such name reads nothing.  Moves ``tpot_p95_ms``."""
import statistics

PROGRAM = "engine_decode"


def read(run):
    t0, t1 = run.trace_window
    steps = sorted(a for name, a, _ in run.trace.modules
                   if PROGRAM in name and t0 <= a <= t1)
    gaps, i, last_end = [], 0, None
    ops = run.trace.ops                      # sorted by start
    for a in steps:
        while i < len(ops) and ops[i][1] < a:
            last_end = ops[i][2] if last_end is None else max(last_end,
                                                              ops[i][2])
            i += 1
        if last_end is not None:
            gaps.append(max(0.0, a - last_end))
    return 1e3 * statistics.median(gaps) if gaps else None
