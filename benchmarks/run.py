"""Benchmark harness entry point — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only SECTION]

Prints ``name,us_per_call,derived`` CSV and writes
benchmarks/results/bench_<section>.json.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))
sys.path.insert(0, os.path.join(HERE, "..", "src"))

SECTIONS = ["schemes", "tiling", "sweep", "kernels", "models"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="all stencils / all archs (slower)")
    ap.add_argument("--only", choices=SECTIONS, default=None)
    args = ap.parse_args()
    from repro import compile_cache
    compile_cache.enable()

    from benchmarks import (bench_kernels, bench_models, bench_schemes,
                            bench_sweep, bench_tiling)
    mods = {
        "schemes": bench_schemes,   # paper Fig. 7 + Table 2
        "tiling": bench_tiling,     # paper Fig. 8 + Table 3
        "sweep": bench_sweep,       # paper Table 4
        "kernels": bench_kernels,   # §3.2/§3.3 kernel evidence
        "models": bench_models,     # LM substrate regression
    }
    os.makedirs(os.path.join(HERE, "results"), exist_ok=True)
    print("name,us_per_call,derived")
    for sec in ([args.only] if args.only else SECTIONS):
        rows = mods[sec].run(full=args.full)
        payload = []
        for r in rows:
            print(r)
            payload.append({"name": r.name, "us_per_call": r.us,
                            "derived": r.derived})
        with open(os.path.join(HERE, "results", f"bench_{sec}.json"),
                  "w") as f:
            json.dump(payload, f, indent=1)


if __name__ == "__main__":
    main()
