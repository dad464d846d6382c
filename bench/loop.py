"""Drives the engine through one run's traffic and records what each
request saw, on the host's clock (``time.perf_counter``).

One thread does everything: it queues each request when it is due, then
runs one engine step, and sleeps only when the engine has nothing to do.
A request is queued at the first pass of the loop after its due time, so
the generator runs late by up to one step; that lateness is recorded, and
every latency is timed from the due time, so it counts.

The measured window is [open, close].  A token counts in it when the call
that emitted it returned inside it; the loop stops at the first pass after
``close`` and does not wait for requests still running.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from bench.traffic import Schedule

now = time.perf_counter


@dataclass
class RequestStat:
    index: int
    prompt_len: int
    due: float                         # host clock
    queued: Optional[float] = None
    first_token: Optional[float] = None
    finished: Optional[float] = None
    handle: object = None              # the engine's request object
    win_first: Optional[tuple] = None  # (time, tokens so far) in the window
    win_last: Optional[tuple] = None


@dataclass
class Window:
    open: float
    close: float
    requests: List[RequestStat]
    lateness: List[float] = field(default_factory=list)   # queued - due
    loop: str = "open"

    @property
    def seconds(self) -> float:
        return self.close - self.open

    def due_in_window(self) -> List[RequestStat]:
        return [r for r in self.requests if self.open <= r.due < self.close]


def _record(stats: Dict[int, RequestStat], events, window) -> None:
    for req, t, n in events:
        st = stats[req.rid]
        if n == 1:
            st.first_token = t
        if req.finished is not None and st.finished is None:
            st.finished = req.finished
        if window[0] <= t <= window[1]:
            if st.win_first is None:
                st.win_first = (t, n)
            st.win_last = (t, n)


def run(adapter, sched: Schedule, seconds: float,
        on_open: Callable[[], None] = lambda: None) -> Window:
    """Serve ``sched`` until ``seconds`` after the window opens."""
    reqs = sched.requests
    stats: Dict[int, RequestStat] = {}
    lateness: List[float] = []
    win = [float("inf"), float("inf")]

    def queue(r, due):
        h = adapter.submit(r.prompt, r.output_len)
        st = RequestStat(r.index, len(r.prompt), due, queued=now(),
                         handle=h)
        stats[h.rid] = st
        lateness.append(st.queued - due)

    if sched.loop == "open":
        start = now()
        win = [start + sched.lead_s, start + sched.lead_s + seconds]
        nxt, opened = 0, False
        while True:
            t = now()
            if t >= win[1]:
                break
            if not opened and t >= win[0]:
                opened = True
                on_open()
            while nxt < len(reqs) and start + reqs[nxt].due <= t:
                queue(reqs[nxt], start + reqs[nxt].due)
                nxt += 1
            if adapter.busy():
                _record(stats, adapter.step(), win)
                continue
            wake = min(win[1], start + reqs[nxt].due if nxt < len(reqs)
                       else win[1])
            if not opened:
                wake = min(wake, win[0])
            with TraceAnnotation("sleep"):
                time.sleep(max(0.0, wake - now()))
        # due before the close but never queued: the last step ran past it
        for r in reqs[nxt:]:
            if start + r.due < win[1]:
                stats[-1 - r.index] = RequestStat(r.index, len(r.prompt),
                                                  start + r.due)
    else:
        nxt = 0

        def top_up():
            nonlocal nxt
            while adapter.n_waiting < sched.depth:
                if nxt == len(reqs):
                    raise RuntimeError("the closed loop ran out of requests; "
                                       "raise the mix's 'requests'")
                t = now()
                queue(reqs[nxt], t)
                nxt += 1

        for _ in range(adapter.slots):
            queue(reqs[nxt], now())
            nxt += 1
        top_up()
        _record(stats, adapter.step(), win)     # fills every slot
        top_up()
        t = now()
        win = [t, t + seconds]
        on_open()
        while now() < win[1]:
            _record(stats, adapter.step(), win)
            top_up()
    return Window(win[0], win[1], sorted(stats.values(), key=lambda s: s.due),
                  lateness, sched.loop)


# ------------------------------------------------------------- metrics --
def ttft_ms(w: Window) -> List[float]:
    """Time to first token of every request due in the window, from its due
    time.  One with no first token by the close counts as close - due."""
    out = []
    for r in w.due_in_window():
        t = r.first_token if r.first_token is not None else w.close
        out.append((min(t, w.close) - r.due) * 1e3)
    return out


def tpot_ms(w: Window) -> List[float]:
    """Per request that emitted two or more tokens in the window: the time
    between its first and last token there over the tokens between."""
    return [(r.win_last[0] - r.win_first[0]) * 1e3
            / (r.win_last[1] - r.win_first[1])
            for r in w.requests
            if r.win_first is not None and r.win_last[1] > r.win_first[1]]


def tokens_in_window(w: Window) -> int:
    return sum(r.win_last[1] - r.win_first[1] + 1
               for r in w.requests if r.win_first is not None)


def p95(values) -> float:
    return float(np.percentile(np.asarray(values, float), 95))
