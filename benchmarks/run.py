"""Benchmark entrypoint: one section per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV (us_per_call = 0 for derived-metric
rows).  ``--fast`` trims the sweeps for CI-speed runs.

  PYTHONPATH=src python -m benchmarks.run [--fast] [--only fig2,fig3,...]
"""
from __future__ import annotations

import argparse
import sys
import traceback

SECTIONS = {
    "tables": "benchmarks.bench_model_stats",
    "fig2": "benchmarks.bench_cs_curve",
    "fig3": "benchmarks.bench_split_latency",
    "fig4": "benchmarks.bench_protocol",
    "micro": "benchmarks.bench_micro",
    "fleet": "benchmarks.bench_fleet",
    "runtime": "benchmarks.bench_runtime",
    "api": "benchmarks.bench_api",
    "pipeline": "benchmarks.bench_pipeline",
    "planner": "benchmarks.bench_planner",
    "megafleet": "benchmarks.bench_megafleet",
    "controller": "benchmarks.bench_controller",
    "obs": "benchmarks.bench_obs",
    "faults": "benchmarks.bench_faults",
    "roofline": "benchmarks.roofline",
    # needs >=32 emulated devices; standalone: python -m benchmarks.bench_multipod_wire
    "multipod_wire": "benchmarks.bench_multipod_wire",
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(SECTIONS))
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(SECTIONS)
    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        mod_name = SECTIONS[name]
        try:
            import importlib
            mod = importlib.import_module(mod_name)
            for row in mod.run(fast=args.fast):
                print(",".join(str(x) for x in row), flush=True)
        except Exception:
            failures += 1
            print(f"{name},ERROR,0", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
