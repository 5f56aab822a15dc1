"""Benchmark entry point — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).

  speed_overhead        — paper Fig. 3 + App Figs 8-9 (inference overhead)
  glue_synthetic        — paper Tables 2 & 5 (method comparison protocol)
  param_efficiency      — paper App Figs 4-7 (params vs accuracy)
  multitask_throughput  — paper §3.1 / Table 1 (multi-task serving)
  weight_analysis       — paper §4.3 / App Tables 7-10 (P row norms)
  kernels               — kernel microbench + FLOP accounting
  roofline              — EXPERIMENTS.md §Roofline table from the dry-run

Flags: --quick trims the training-based sections; --only <section>.
"""
from __future__ import annotations

import argparse
import sys
import traceback

SECTIONS = ["kernels", "speed_overhead", "multitask_throughput",
            "weight_analysis", "param_efficiency", "glue_synthetic",
            "roofline"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=SECTIONS)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sections = [args.only] if args.only else SECTIONS
    print("name,us_per_call,derived")
    failures = []
    for s in sections:
        try:
            if s == "kernels":
                from benchmarks import kernels_bench
                kernels_bench.run()
            elif s == "speed_overhead":
                from benchmarks import speed_overhead
                speed_overhead.run()
            elif s == "multitask_throughput":
                from benchmarks import multitask_throughput
                multitask_throughput.run()
            elif s == "weight_analysis":
                from benchmarks import weight_analysis
                weight_analysis.run(steps=80 if args.quick else 150)
            elif s == "param_efficiency":
                from benchmarks import param_efficiency
                param_efficiency.run(steps=60 if args.quick else 120)
            elif s == "glue_synthetic":
                from benchmarks import glue_synthetic
                glue_synthetic.run(seeds=(0,) if args.quick else (0, 1),
                                   steps=60 if args.quick else 120)
            elif s == "roofline":
                from benchmarks import roofline_table
                # baseline (paper-faithful) single-pod, then the optimized
                # config on both production meshes
                roofline_table.run("results/dryrun", tag="pod1")
                roofline_table.run("results/dryrun_opt", tag="pod1")
                roofline_table.run("results/dryrun_opt", tag="pod2")
        except Exception:
            failures.append(s)
            traceback.print_exc()
    if failures:
        print(f"FAILED sections: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
