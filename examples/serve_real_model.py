"""End-to-end driver: REAL serving of a small model with batched requests,
then the simulator predicting the same system (paper Table-2 protocol).

    PYTHONPATH=src python examples/serve_real_model.py
"""
from repro.launch.serve import run


def main():
    out = run("qwen2-7b", smoke=True, n_requests=4, prompt_min=32,
              prompt_max=32, output_len=24)
    m, p = out["measured"], out["predicted"]
    print("real MiniEngine (JAX):")
    print(f"  throughput {m['throughput_tok_s']:8.1f} tok/s   "
          f"ttft {m['ttft_mean_s']*1e3:7.1f} ms   "
          f"tpot {m['tpot_mean_s']*1e3:6.1f} ms")
    print(f"Frontier simulation ({out['hardware']} hardware profile):")
    print(f"  throughput {p['throughput_tok_s']:8.1f} tok/s   "
          f"ttft {p['ttft_p50_s']*1e3:7.1f} ms   "
          f"tpot {p['tpot_p50_s']*1e3:6.1f} ms")
    err = abs(p["throughput_tok_s"] - m["throughput_tok_s"]) \
        / m["throughput_tok_s"]
    print(f"relative error: {err:.1%}")


if __name__ == "__main__":
    main()
