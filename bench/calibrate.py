"""The readings a cell's limit is set from: in one process (one backend
start), one run of the cell per seed, each read against the reference and
against the float8 control on the same sample.

    python3 bench/calibrate.py --workload mixtral-8x7b.chat \
        --seeds 101,102,103 --seconds 51

Prints one line per seed: the program's widest and mean gaps (the lower
reading comes from the largest over the seeds), the control's (the upper
from the smallest), the verdict of the cell's limits on each (``correct``
and ``control_correct``: the control has to read false), the tokens and
requests compared, and the run's metrics.
The limit then lies between the two, as PERF.md records.  Each seed's
per-position gaps, the program's and the control's, are saved to
``chiprun_out/calibrate_<workload>_<seed>.npz``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    import numpy as np
    from bench import correct, run, spec
    limits = spec.load_cell(args.workload).traffic["check"]
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        out, r = run.run_cell(args.workload, seed, args.seconds, False,
                              control=True)
        checks = correct.verdict(r.control, limits)
        control_ok = all(c["value"] <= c["limit"] for c in checks.values())
        print("CALIBRATE " + json.dumps({
            "workload": args.workload, "seed": seed,
            **{n: getattr(r, n) for n in correct.NUMBERS},
            "control": {n: getattr(r.control, n) for n in correct.NUMBERS},
            "tokens": r.tokens, "requests": r.requests,
            "correct": out["correct"], "control_correct": control_ok,
            "metrics": {k: v["value"] for k, v in out["metrics"].items()}}),
            flush=True)
        np.savez(out_dir / f"calibrate_{args.workload}_{seed}.npz", **r.raw)


if __name__ == "__main__":
    main()
