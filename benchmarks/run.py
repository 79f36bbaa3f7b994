"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (the harness contract).

    PYTHONPATH=src python -m benchmarks.run [--only fig5]
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark names")
    args = ap.parse_args()

    from benchmarks import figures
    benches = [
        ("fig1a_linear_latency", figures.fig1a_linear_latency),
        ("fig1b_attention_latency", figures.fig1b_attention_latency),
        ("fig5_throughput", figures.fig5_throughput),
        ("fig6_latency", figures.fig6_latency),
        ("fig7_output_length", figures.fig7_output_length),
        ("ineq_regime", figures.ineq_regime),
        ("perf_model_accuracy", figures.perf_model_accuracy),
        ("overlap_microbench", figures.overlap_microbench),
    ]
    print("name,us_per_call,derived")
    for name, fn in benches:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            for row_name, us, derived in fn():
                print(f"{row_name},{us:.2f},{derived}")
        except Exception as e:  # keep the harness running
            print(f"{name},NaN,ERROR:{type(e).__name__}:{e}", file=sys.stderr)
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
