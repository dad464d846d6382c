"""The profiler's trace of a run, reduced to what the per-layer metrics
read.

``capture`` runs JAX's profiler around the loop.  ``load`` reads the
``.xplane.pb`` it wrote into plain lists, on the trace's own clock:

- ``ops``: every operation that ran on a device (a ``/device:...`` plane's
  "XLA Ops" line), as (name, start, end) in seconds;
- ``modules``: every program that ran on a device ("XLA Modules" line);
- ``spans``: the host annotations the benchmark wrote ("prefill", "decode",
  "sleep", "window"), as (kind, id, start, end).

Where no device plane exists (the CPU backend), operations are read from
the host threads that ran them, by their ``hlo_op`` stat; that serves the
tests only, since a run without an accelerator stops before it traces.
The reductions below work on those lists alone.
"""
from __future__ import annotations

import bisect
import glob
import os
import shutil
import tempfile
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

KINDS = ("prefill", "decode", "sleep", "window")


@dataclass
class Trace:
    ops: List[Tuple[str, float, float]] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)
    spans: List[Tuple[str, int, float, float]] = field(default_factory=list)
    devices: int = 1
    on_device: bool = True    # False: operations read from host threads
    offset: float = 0.0       # device clock minus host clock, seconds

    @property
    def window(self) -> Tuple[float, float]:
        w = [s for s in self.spans if s[0] == "window"]
        if len(w) != 1:
            raise ValueError(f"{len(w)} window spans in the trace")
        return w[0][2], w[0][3]


class capture:
    """Context manager: profile into a fresh temporary directory; ``path``
    is the trace file once it has exited."""

    def __enter__(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # no event per Python call
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        self.path = found[0] if found else None
        return False

    def cleanup(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return align(from_planes(ProfileData.from_file(path).planes))


def clock_offset(tr: Trace, reach: float = 0.5) -> float:
    """Seconds by which the device's clock reads later than the host's.

    The profiler puts device events on a clock that can sit tens of
    milliseconds off the host's (31-33 ms on a v5e).  Each engine call waits
    for its program, so on one clock a program lies inside the host span
    that issued it: program (a, b) in span (s, e) means the offset lies in
    [b - e, a - s].  The offset taken is the middle of the stretch that the
    most (program, span) pairs within ``reach`` seconds allow."""
    spans = sorted(s for s in tr.spans if s[0] in ("prefill", "decode"))
    spans.sort(key=lambda s: s[2])
    starts = [s[2] for s in spans]
    edges = []
    for _, a, b in tr.modules:
        lo = bisect.bisect_left(starts, a - reach)
        hi = bisect.bisect_right(starts, a + reach)
        for _, _, s, e in spans[lo:hi]:
            if b - e <= a - s:
                edges += [(b - e, 1), (a - s, -1)]
    if not edges:
        return 0.0
    edges.sort(key=lambda x: (x[0], -x[1]))
    best, depth, where = 0, 0, (0.0, 0.0)
    for i, (x, d) in enumerate(edges):
        depth += d
        if d > 0 and depth > best:
            best, where = depth, (x, edges[i + 1][0])
    return (where[0] + where[1]) / 2


def align(tr: Trace) -> Trace:
    """Put the device's events on the host's clock."""
    if not tr.on_device:
        return tr
    off = clock_offset(tr)
    tr.offset = off
    tr.ops = [(n, a - off, b - off) for n, a, b in tr.ops]
    tr.modules = [(n, a - off, b - off) for n, a, b in tr.modules]
    return tr


def op_name(hlo: str) -> str:
    """``%fusion.225 = bf16[...] fusion(...)`` -> ``fusion.225``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def from_planes(planes) -> Trace:
    """Read planes (``ProfileData.planes``, or anything shaped alike: each
    with ``name`` and ``lines``, each line with ``name`` and ``events``,
    each event with ``name``, ``start_ns``, ``duration_ns`` and ``stats``)."""
    planes = list(planes)
    tr = Trace()
    device_planes = [p for p in planes if p.name.startswith("/device:")]
    tr.on_device = bool(device_planes)
    chips = set()
    for plane in planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                t0 = ev.start_ns * 1e-9
                t1 = t0 + ev.duration_ns * 1e-9
                if on_device:
                    if line.name == "XLA Ops":
                        tr.ops.append((op_name(ev.name), t0, t1))
                        chips.add(plane.name)
                    elif line.name == "XLA Modules":
                        tr.modules.append((ev.name, t0, t1))
                    continue
                if ev.name in KINDS:
                    stats = dict(ev.stats)
                    tr.spans.append((ev.name, int(stats.get("id", -1)),
                                     t0, t1))
                elif not device_planes:
                    stats = dict(ev.stats)
                    if "hlo_op" in stats:
                        tr.ops.append((ev.name, t0, t1))
                        tr.modules.append((str(stats.get("hlo_module", "")),
                                           t0, t1))
    # a chip is a device plane with operations (there are others, without)
    tr.devices = max(1, len(chips))
    for lst in (tr.ops, tr.modules, tr.spans):
        lst.sort(key=lambda e: e[-2])
    return tr


# ------------------------------------------------------------ reductions --
def union(intervals, t0: float, t1: float) -> List[Tuple[float, float]]:
    """Merged intervals of (.., start, end) clipped to [t0, t1]."""
    out: List[List[float]] = []
    for iv in sorted(intervals, key=lambda e: e[-2]):
        a, b = max(iv[-2], t0), min(iv[-1], t1)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(tr: Trace, t0: float, t1: float) -> float:
    """Seconds in [t0, t1] in which an operation ran, averaged over the
    devices traced."""
    return sum(b - a for a, b in union(tr.ops, t0, t1)) / tr.devices


class SpanIndex:
    """The host span of a kind that contains a time."""

    def __init__(self, spans, kinds):
        self.spans = [s for s in spans if s[0] in kinds]
        self.starts = [s[2] for s in self.spans]

    def at(self, t: float):
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and self.spans[i][2] <= t <= self.spans[i][3]:
            return self.spans[i]
        return None


def program_time(tr: Trace, t0: float, t1: float) -> Dict[int, float]:
    """Device seconds of the programs each host span issued: span id ->
    seconds, for programs that started in [t0, t1].  The insert a prefill
    issues runs after its span ends and is not counted here."""
    idx = SpanIndex(tr.spans, ("prefill", "decode"))
    out: Dict[int, float] = defaultdict(float)
    for name, a, b in tr.modules:
        if not t0 <= a <= t1:
            continue
        span = idx.at(a)
        # the slot-cache insert a prefill issues is known by its name
        if span is not None and "insert_slot" not in name:
            out[span[1]] += b - a
    return dict(out)


def breakdown(tr: Trace, t0: float, t1: float, n: int = 10) -> Dict:
    """The device operations that took most time, named by the engine call
    they ran under, and the longest idle gaps, each named by what the host
    was doing in it and when (seconds after ``t0``)."""
    idx = SpanIndex(tr.spans, ("prefill", "decode"))
    ops: Dict[str, float] = defaultdict(float)
    for name, a, b in tr.ops:
        if t0 <= a <= t1:
            span = idx.at(a)
            ops[f"{span[0] if span else 'other'}:{name}"] += b - a
    host = SpanIndex(tr.spans, ("prefill", "decode", "sleep"))
    gaps = []
    prev = t0
    for a, b in union(tr.ops, t0, t1) + [(t1, t1)]:
        if a > prev:
            span = host.at((prev + a) / 2)
            gaps.append([f"{span[0] if span else 'loop'} at "
                         f"{prev - t0:.3f} s", a - prev])
        prev = max(prev, b)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:n]}
