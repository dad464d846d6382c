"""Find a mix's knee on the chip: serve it at several fixed rates in one
process (one set-up) and print, for each rate, the tails and whether a
backlog grew.

    python3 bench/sweep.py --workload mixtral-8x7b.chat --rates 1,1.5,2 \
        --seconds 30 [--seed 7]

A backlog is the number of requests due and still without a first token.
It grows when it is larger at the window's close than at its middle; the
knee is the highest rate at which it does not.  Each rate runs the mix's
own lengths and lead, with only ``rate_rps`` changed; the engine is emptied
between rates.  Nothing is checked against the reference here.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def backlog(w, t: float) -> int:
    return sum(1 for r in w.requests if r.due < t and (
        r.first_token is None or r.first_token > t))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    import numpy as np
    from bench import loop, run, spec, traffic
    from bench.engine_adapter import Adapter
    cell = spec.load_cell(args.workload)
    run.enable_cache()
    run.devices_for(cell.chips)
    t = time.perf_counter()
    adapter = Adapter(cell.config, args.seed)
    scheds = {}
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_rps=rate)
        scheds[rate] = traffic.schedule(mix, cell.config["vocab_size"],
                                        args.seed, args.seconds)
    adapter.warm_up(sorted({len(r.prompt) for s in scheds.values()
                            for r in s.requests}))
    print(f"[sweep] {args.workload}: set-up {time.perf_counter() - t:.1f} s, "
          f"{args.seconds:.0f} s windows, seed {args.seed}", flush=True)
    print("rate_rps  due  ttft_p50_ms  ttft_p95_ms  tpot_p50_ms  tpot_p95_ms"
          "  tok_s  backlog_mid  backlog_close", flush=True)
    for rate, sched in scheds.items():
        w = loop.run(adapter, sched, args.seconds)
        ttft, tpot = loop.ttft_ms(w), loop.tpot_ms(w)
        q = lambda v, p: float(np.percentile(v, p)) if v else float("nan")  # noqa: E731
        print(f"{rate:8.2f} {len(ttft):4d} {q(ttft, 50):12.1f} {q(ttft, 95):12.1f}"
              f" {q(tpot, 50):12.2f} {q(tpot, 95):12.2f} "
              f"{loop.tokens_in_window(w) / w.seconds:6.1f} "
              f"{backlog(w, (w.open + w.close) / 2):12d} "
              f"{backlog(w, w.close):14d}", flush=True)
        adapter.reset()


if __name__ == "__main__":
    main()
