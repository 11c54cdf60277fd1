# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Paper-table runner: `PYTHONPATH=src python -m benchmarks.run [--only X]`.

Paper artifacts:   table1 (Table I), table3 (Table III), fig3 (Fig. 3),
                   fig4 (Fig. 4), table456 (Tables IV-VI), sgd_amtl (§V)

These are CPU reproductions of the paper's tables.  The chip's numbers
come from `python3 -m bench.run` (BENCHMARK.json).
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (fig3_scaling, fig4_convergence, sgd_amtl,
                            table1_timing, table3_public,
                            table456_dynamic_step)
    suites = {
        "table1": table1_timing.run,
        "table3": table3_public.run,
        "fig3": fig3_scaling.run,
        "fig4": fig4_convergence.run,
        "table456": table456_dynamic_step.run,
        "sgd_amtl": sgd_amtl.run,
    }
    names = args.only.split(",") if args.only else list(suites)

    print("name,us_per_call,derived")
    for name in names:
        for row in suites[name]():
            print(row.csv())
        sys.stdout.flush()


if __name__ == "__main__":
    main()
