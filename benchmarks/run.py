# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark runner — one module per paper table/figure (DESIGN.md §6).

  PYTHONPATH=src python -m benchmarks.run [--fast] [--only NAME]
"""

import argparse
import sys
import time

from . import (bench_candidates, bench_costmodel, bench_exec_time,
               bench_kernels, bench_lk_counts, bench_phase_breakdown,
               bench_rules, bench_scalability, bench_scaling, bench_speedup,
               bench_stream)

SUITES = {
    "exec_time": bench_exec_time,          # Figs. 2-4
    "phase_breakdown": bench_phase_breakdown,  # Tables 3-5, 10-12
    "lk_counts": bench_lk_counts,          # Table 6
    "candidates": bench_candidates,        # Tables 7-9
    "scalability": bench_scalability,      # Fig. 5(a)
    "speedup": bench_speedup,              # Fig. 5(b)
    "kernels": bench_kernels,              # Pallas/counting microbench
    "rules": bench_rules,                  # rule generation + serving (§7)
    "stream": bench_stream,                # streaming incremental mining (§8)
    "costmodel": bench_costmodel,          # calibrated cost model (§9)
    "scaling": bench_scaling,              # device-count scaling curves (§11)
}


# the CI pass: pipeline A/B + kernels + rule subsystem + streaming + costmodel
SMOKE_SUITES = ("exec_time", "kernels", "rules", "stream", "costmodel")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced datasets/algorithms (CI-sized)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: --fast sizes, exec_time + kernels only")
    ap.add_argument("--only", default=None, choices=sorted(SUITES))
    args = ap.parse_args()

    if args.smoke:
        args.fast = True
    suites = {args.only: SUITES[args.only]} if args.only else (
        {k: SUITES[k] for k in SMOKE_SUITES} if args.smoke else SUITES)
    t0 = time.time()
    failed = []
    for name, mod in suites.items():
        print(f"== {name} ==", flush=True)
        try:
            mod.run(fast=args.fast)
        except Exception as e:  # finish the other suites, then exit non-zero
            failed.append(name)
            print(f"name,us_per_call,derived\n{name}/FAILED,0,{type(e).__name__}: {e}\n",
                  flush=True)
    print(f"# total benchmark wall time: {time.time() - t0:.1f}s")
    if failed:
        sys.exit(f"benchmark suites failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
