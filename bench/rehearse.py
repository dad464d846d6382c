"""Compile a configuration's engine programs for a described TPU v5e,
without the chip, and print what each would hold on the device.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --config qwen2-7b \
        [--layers 22] [--slots 16] [--buckets 2048,1024]

The estimate of the peak is the largest over the programs of what lives
while each runs: the weights and the slot cache, which live for the whole
run, and the program's temporaries and outputs that do not alias an
argument (the weight draw runs before the cache exists).  It is a rehearsal, not a chip run.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

GiB = 2 ** 30


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--layers", type=int)
    ap.add_argument("--slots", type=int)
    ap.add_argument("--buckets", default="2048")
    args = ap.parse_args()
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from bench.engine_adapter import compile_for
    from bench.spec import BENCH_DIR, load_json
    conf = load_json(BENCH_DIR / "configs" / f"{args.config}.json")
    if args.layers:
        conf["num_hidden_layers"] = args.layers
    if args.slots:
        conf["serving"]["slots"] = args.slots
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    progs, w, c = compile_for(conf, one,
                              [int(b) for b in args.buckets.split(",")])
    peaks = {}
    for name, p in progs.items():
        ma = p.memory_analysis()
        # the weights are drawn before the cache exists; every other
        # program runs beside the weights and the cache
        peaks[name] = (w + ma.temp_size_in_bytes if name == "weights" else
                       w + c + ma.temp_size_in_bytes + max(
                           0, ma.output_size_in_bytes
                           - ma.alias_size_in_bytes))
        print(f"{name:14s} args {ma.argument_size_in_bytes / GiB:7.3f} GiB  "
              f"temp {ma.temp_size_in_bytes / GiB:7.3f}  out "
              f"{ma.output_size_in_bytes / GiB:7.3f}  alias "
              f"{ma.alias_size_in_bytes / GiB:7.3f}")
    peak = max(peaks.values())
    print(f"{conf['name']}: {conf['num_hidden_layers']} layers, "
          f"{conf['serving']['slots']} slots x {conf['serving']['max_seq']}: "
          f"weights {w / GiB:.3f} GiB, cache {c / GiB:.3f} GiB, peak "
          f"estimate {peak / GiB:.3f} GiB of 15.75")


if __name__ == "__main__":
    main()
