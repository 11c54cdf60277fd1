"""The benchmark's one command.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json in this process, on the chips of this
machine, and prints one JSON object as the last line of standard output:
`correct`, `attempted`, `failed`, `metrics` (the cell's end-to-end metrics
with --trace 0, its per-layer metrics with --trace 1), `device`, with
--trace 1 `breakdown`, and last `checks`, each compared number beside its
limit.  Without a TPU, or with fewer chips than the cell asks for, it
exits 3 and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness, spec

    cell = spec.cell(args.workload)
    try:
        devices = harness.tpu_devices(cell.chips)
    except harness.NoAccelerator as e:
        print(f"bench.run: {e}", file=sys.stderr)
        return 3

    harness.enable_cache()
    result, _ = harness.execute(cell, args.seed, args.seconds,
                                bool(args.trace), T_PROCESS, devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
