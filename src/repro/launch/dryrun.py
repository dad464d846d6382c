import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The lines above MUST run before any other import (jax locks the platform
# and device count at first init).  The dry run lowers for 512 host CPU
# devices and never takes an attached chip.  Do not move them.

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from pathlib import Path  # noqa: E402

import jax           # noqa: E402

from repro.configs import ARCH_IDS, SHAPES, get_config, shape_applicable  # noqa: E402
from repro.launch import hlo_cost  # noqa: E402
from repro.launch.lowering import build_step, lower_step  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402

ARTIFACT_DIR = Path(os.environ.get("REPRO_ARTIFACTS", "artifacts")) / "dryrun"


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             remat: str = "none", tag: str = "", options: dict = None) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multipod" if multi_pod else "pod",
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    bundle = build_step(cfg, mesh, shape, remat=remat, options=options)
    lowered = lower_step(bundle, mesh)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0] if ca else {}
    txt = compiled.as_text()
    corrected = hlo_cost.analyze(txt)
    n_chips = mesh.devices.size

    # memory_analysis() prints per-device stats — record the key fields
    mem_rec = {
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "generated_code_bytes": mem.generated_code_size_in_bytes,
    }
    print(f"[{arch} x {shape_name} x {'multipod' if multi_pod else 'pod'}] "
          f"compiled in {t2 - t1:.1f}s (lower {t1 - t0:.1f}s)")
    print("  memory_analysis:", mem_rec)
    print("  cost_analysis: flops=%.3e bytes=%.3e" % (
        ca.get("flops", 0.0), ca.get("bytes accessed", 0.0)))
    print("  scan-corrected: flops=%.3e bytes=%.3e coll=%.3e" % (
        corrected["flops"], corrected["bytes"], corrected["collective_bytes"]))

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "multipod" if multi_pod else "pod",
        "status": "ok",
        "tag": tag,
        "n_chips": n_chips,
        "step": bundle.name,
        "meta": bundle.meta,
        "lower_s": t1 - t0,
        "compile_s": t2 - t1,
        "memory_analysis": mem_rec,
        "cost_analysis_raw": {k: float(v) for k, v in ca.items()
                              if isinstance(v, (int, float))},
        "hlo_corrected": {k: float(v) for k, v in corrected.items()},
    }
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description="Multi-pod dry-run driver")
    ap.add_argument("--arch", default="all",
                    help=f"one of {ARCH_IDS} or 'all'")
    ap.add_argument("--shape", default="all",
                    help=f"one of {list(SHAPES)} or 'all'")
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--remat", default="none")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--attn", default="naive", choices=["naive", "blockwise"])
    ap.add_argument("--attn-block", type=int, default=1024)
    ap.add_argument("--rwkv", default="scan", choices=["scan", "chunked"])
    ap.add_argument("--rwkv-chunk", type=int, default=16)
    ap.add_argument("--moe", default="psum", choices=["psum", "a2a"])
    ap.add_argument("--out", default=str(ARTIFACT_DIR))
    args = ap.parse_args()
    options = {"attn_impl": args.attn, "attn_block": args.attn_block,
               "rwkv_impl": args.rwkv, "rwkv_chunk": args.rwkv_chunk,
               "moe_dispatch": args.moe}

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "multipod" if mp else "pod"
                fname = outdir / f"{arch}__{shape}__{mesh_name}__{args.tag}.json"
                try:
                    rec = run_cell(arch, shape, mp, remat=args.remat,
                                   tag=args.tag, options=options)
                except Exception as e:  # a failing cell is a bug — record it
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "status": "error", "error": f"{type(e).__name__}: {e}"}
                    failures.append((arch, shape, mesh_name))
                fname.write_text(json.dumps(rec, indent=2))
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)
    print("dry-run complete:", outdir)


if __name__ == "__main__":
    main()
