"""Where the device's idle time before each decode step goes, by
MiniEngine's own spans: one traced run of a cell, with the engine's
``repro.obs.Telemetry`` attached after warm-up.

    python3 bench/engine_split.py --workload qwen2-7b.decode_backlog \
        --seed 3141592653 --seconds 51 [--telemetry 0]

Each gap is the stretch ``decode_host_gap_ms`` measures: from the latest end
among the device operations that began before a decode program (the module
named ``engine_decode``) to that program's start.  The engine's spans
(``engine.*`` annotations, on the same clock once ``bench/trace.py`` has
aligned the device) cover it in parts: the decode call's input upload, its
program dispatch and its bookkeeping, a prefill's phases, the rest of the
admit and decode calls, and what lies outside every engine call.  The parts
are averaged over the gaps longer than zero, and the line says how many of
the window's decode steps had one.

Also read from the same run: the cell's per-layer metrics, the median host
time of a decode call (every decode call after warm-up), the prefills'
expert-row share (an MoE model: the rows their real tokens need over the
rows the expert GEMMs compute), and which step programs compiled in the
window.  ``--telemetry 0`` makes the same traced run with no recorder
attached; against it, the run with one gives the recorder's cost.  Prints
one JSON line.
"""
from __future__ import annotations

import argparse
import bisect
import json
import statistics
import sys
from pathlib import Path
from typing import Any, Dict, List, Tuple

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

PROGRAM = "engine_decode"
LEAVES = {"decode": ("decode.inputs", "decode.program", "decode.bookkeep"),
          "prefill": ("prefill.inputs", "prefill.program", "prefill.insert")}

Event = Tuple[str, Dict[str, Any], float, float]   # kind, stats, start, end


def engine_events(planes) -> List[Event]:
    """The engine's annotations (``engine.<kind>``) on the host's planes,
    as (kind, stats, start, end) in seconds, sorted by start."""
    out = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    t0 = ev.start_ns * 1e-9
                    out.append((ev.name[len("engine."):], dict(ev.stats), t0,
                                t0 + ev.duration_ns * 1e-9))
    return sorted(out, key=lambda e: e[2])


def decode_gaps(tr, t0: float, t1: float) -> Tuple[int, List[Tuple]]:
    """The decode steps starting in [t0, t1], and the device's idle gaps
    before them, as (start, end), those longer than zero."""
    steps = sorted(a for name, a, _ in tr.modules
                   if PROGRAM in name and t0 <= a <= t1)
    gaps, i, last = [], 0, None
    for a in steps:
        while i < len(tr.ops) and tr.ops[i][1] < a:
            last = tr.ops[i][2] if last is None else max(last, tr.ops[i][2])
            i += 1
        if last is not None and a > last:
            gaps.append((last, a))
    return len(steps), gaps


class Cover:
    """Time that the events of one kind (which never overlap) cover in a
    stretch."""

    def __init__(self, events: List[Event], kind: str):
        spans = sorted((a, b) for k, _, a, b in events if k == kind)
        self.starts = [a for a, _ in spans]
        self.ends = [b for _, b in spans]

    def __call__(self, g0: float, g1: float) -> float:
        lo = bisect.bisect_right(self.ends, g0)
        hi = bisect.bisect_left(self.starts, g1)
        return sum(min(self.ends[j], g1) - max(self.starts[j], g0)
                   for j in range(lo, hi))


def gap_split(gaps: List[Tuple], events: List[Event]) -> Dict[str, float]:
    """Each part of the gaps, in ms, averaged over the gaps."""
    kinds = [k for ks in LEAVES.values() for k in ks]
    cover = {k: Cover(events, k) for k in kinds + ["decode", "prefill",
                                                    "admit"]}
    total = dict.fromkeys(kinds + ["decode.other", "prefill.other",
                                   "admit.other", "outside"], 0.0)
    for g0, g1 in gaps:
        c = {k: f(g0, g1) for k, f in cover.items()}
        for call, leaves in LEAVES.items():
            for k in leaves:
                total[k] += c[k]
            total[f"{call}.other"] += c[call] - sum(c[k] for k in leaves)
        total["admit.other"] += c["admit"] - c["prefill"]
        total["outside"] += (g1 - g0) - c["admit"] - c["decode"]
    return {k: 1e3 * v / len(gaps) for k, v in total.items()} if gaps else {}


def expert_useful_share(events: List[Event], t0: float, t1: float):
    """Sum of the rows the prefills' real tokens need over the sum of the
    rows their expert GEMMs compute, in %, over the engine prefill spans
    starting in [t0, t1]; None where none carries the counts."""
    pre = [s for k, s, a, _ in events
           if k == "prefill" and "expert_rows" in s and t0 <= a <= t1]
    if not pre:
        return None
    return 100.0 * (sum(s["expert_rows_needed"] for s in pre)
                    / sum(s["expert_rows"] for s in pre))


def traced_run(workload: str, seed: int, seconds: float,
               telemetry: bool) -> Dict[str, Any]:
    """One traced run of the cell, read as the module says."""
    from jax.profiler import ProfileData

    from bench import engine_adapter, run
    from bench import trace as trace_mod
    from repro.obs import Telemetry

    held: Dict[str, Any] = {}
    adapter, load = engine_adapter.Adapter, trace_mod.load

    class Attaching(adapter):
        def warm_up(self, prompt_lens):
            n = super().warm_up(prompt_lens)
            held["spans"] = self.spans
            if telemetry:
                self.engine.telemetry = held["tel"] = Telemetry()
            return n

    def load_with_engine(path):
        planes = list(ProfileData.from_file(path).planes)
        held["events"] = engine_events(planes)
        held["trace"] = trace_mod.align(trace_mod.from_planes(planes))
        return held["trace"]

    # run_cell builds its Adapter and loads its trace through these names
    engine_adapter.Adapter, trace_mod.load = Attaching, load_with_engine
    try:
        out, _ = run.run_cell(workload, seed, seconds, True)
    finally:
        engine_adapter.Adapter, trace_mod.load = adapter, load

    tr, events = held["trace"], held["events"]
    t0, t1 = tr.window
    steps, gaps = decode_gaps(tr, t0, t1)
    calls = [s.t1 - s.t0 for s in held["spans"] if s.kind == "decode"]
    return {"workload": workload, "seed": seed, "telemetry": telemetry,
            "correct": out["correct"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "busy_s": out["device"]["busy_s"],
            "window_s": out["device"]["window_s"],
            "host_decode_call_ms_median": 1e3 * statistics.median(calls),
            "host_decode_calls": len(calls),
            "decode_steps": steps, "gaps_above_zero": len(gaps),
            "gap_mean_ms": (1e3 * sum(b - a for a, b in gaps) / len(gaps)
                            if gaps else None),
            "gap_split_mean_ms": gap_split(gaps, events),
            "prefill_expert_useful_share":
                expert_useful_share(events, t0, t1),
            "compiled_in_window": sorted(
                {(k, s["compiled"]) for k, s, a, _ in events
                 if "compiled" in s and t0 <= a <= t1}),
            "spans_recorded": len(held["tel"].spans) if telemetry else 0}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--telemetry", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    print(json.dumps(traced_run(args.workload, args.seed, args.seconds,
                                bool(args.telemetry))), flush=True)


if __name__ == "__main__":
    main()
