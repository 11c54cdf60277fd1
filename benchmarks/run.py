# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark driver: `PYTHONPATH=src python -m benchmarks.run [--only X]`.

Paper artifacts:   table1 (Table I), table3 (Table III), fig3 (Fig. 3),
                   fig4 (Fig. 4), table456 (Tables IV-VI)
Beyond paper:      kernels (fusion microbench), serving (learn-while-serve
                   request throughput + predict latency), roofline (from
                   dry-run JSONL, printed if the file exists)
"""
from __future__ import annotations

import argparse
import os
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed reps per amtl_events row (best-of; the "
                         "±25%% machine-noise caveat in ROADMAP shrinks "
                         "with more reps — raise on noisy CI runners)")
    args = ap.parse_args()
    if args.repeats < 1:
        ap.error("--repeats must be >= 1")

    import functools

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (amtl_events, fig3_scaling, fig4_convergence,
                            kernels_bench, serving, sgd_amtl, table1_timing,
                            table3_public, table456_dynamic_step)
    suites = {
        "table1": table1_timing.run,
        "table3": table3_public.run,
        "fig3": fig3_scaling.run,
        "fig4": fig4_convergence.run,
        "table456": table456_dynamic_step.run,
        "sgd_amtl": sgd_amtl.run,
        "kernels": kernels_bench.run,
        "amtl_events": functools.partial(amtl_events.run,
                                         repeats=args.repeats),
        "serving": functools.partial(serving.run, repeats=args.repeats),
    }
    names = args.only.split(",") if args.only else list(suites)

    print("name,us_per_call,derived")
    for name in names:
        for row in suites[name]():
            print(row.csv())
        sys.stdout.flush()

    if (os.path.exists("dryrun_single_unrolled.jsonl")
            or os.path.exists("dryrun_single.jsonl")) and (
            args.only is None or "roofline" in names):
        from benchmarks import roofline
        print("\n# Roofline (single-pod; cost terms from the unrolled "
              "dry-run, temp bytes from the production-scan dry-run)")
        print(roofline.report())


if __name__ == "__main__":
    main()
