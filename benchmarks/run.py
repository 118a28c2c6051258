"""Benchmark driver: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only NAME]

Emits ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller streams (CI)")
    ap.add_argument("--only", default="",
                    help="comma-separated module names")
    args = ap.parse_args()

    from repro import compile_cache
    compile_cache.enable()
    from benchmarks import (ablations, accuracy, compound_queries,
                            higgs_perf, irregularity, latency,
                            param_sweep, roofline, space, throughput)

    scale = 0.25 if args.fast else 1.0

    def n(base):
        return max(int(base * scale), 20_000)

    suites = {
        "accuracy": lambda: accuracy.run(n_edges=n(120_000)),
        "latency": lambda: latency.run(n_edges=n(120_000)),
        "compound_queries": lambda: compound_queries.run(
            n_edges=n(80_000)),
        "irregularity": lambda: irregularity.run(n_edges=n(60_000)),
        "throughput": lambda: throughput.run(n_edges=n(100_000)),
        "space": lambda: space.run(),
        "ablations": lambda: ablations.run(n_edges=n(50_000)),
        "param_sweep": lambda: param_sweep.run(n_edges=n(60_000)),
        "higgs_perf": lambda: higgs_perf.run(n_edges=n(40_000)),
        "roofline": roofline.run,
    }
    only = {s for s in args.only.split(",") if s}
    broken = []
    for name, fn in suites.items():
        if only and name not in only:
            continue
        print(f"=== {name} ===", flush=True)
        t0 = time.time()
        try:
            fn()
        except Exception as e:  # keep the suite running; report the break
            print(f"{name},0.00,ERROR={type(e).__name__}:{e}", flush=True)
            broken.append(name)
        print(f"=== {name} done in {time.time() - t0:.1f}s ===", flush=True)
    if broken:
        print(f"broken suites: {','.join(broken)}", file=sys.stderr)
    return 1 if broken else 0


if __name__ == "__main__":
    sys.exit(main())
