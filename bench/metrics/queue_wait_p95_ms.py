"""Scheduler: the 95th percentile over requests due in the window of the
wait from the due time to the start of the request's prefill (host clock,
the adapter's spans).  A request not yet prefilled at the close counts as
close - due.  Moves ``ttft_p95_ms``."""
import numpy as np


def read(run):
    w = run.window
    start = {s.info["rid"]: s.t0 for s in run.spans if s.kind == "prefill"}
    waits = []
    for r in w.due_in_window():
        t = start.get(getattr(r.handle, "rid", None), w.close)
        waits.append((min(t, w.close) - r.due) * 1e3)
    return float(np.percentile(waits, 95)) if waits else None
