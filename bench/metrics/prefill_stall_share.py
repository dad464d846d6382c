"""Scheduler: the share of the window, in %, spent inside prefill calls
(each with the insert it issues) while another slot holds a request that
is decoding and so waits (host clock, the adapter's spans).  Moves
``tpot_p95_ms``."""


def read(run):
    w = run.window
    stalled = sum(max(0.0, min(s.t1, w.close) - max(s.t0, w.open))
                  for s in run.spans
                  if s.kind == "prefill" and s.info["decoding"] > 0)
    return 100.0 * stalled / w.seconds
