"""Readings that set a cell's limits: the program and the control.

    python3 -m bench.control --workload <cell> --seeds 1 2 3 ... \
        [--control-seeds 1 2 3]

In this one process: for each of `--seeds` a short run of the cell, and for
each of `--control-seeds` (all of `--seeds` if not given) a short run with
the control (the reference with every product in three bfloat16 passes)
in the program's place, each judged by the harness's own `correct`
against the f32 reference.  Prints one JSON line per run.  The lower reading of a limit is the
largest program number over a dozen seeds or more, the upper reading the
smallest control number; bench/limits/<cell>.json holds the limit set
between them.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))


def reading(cell, seed: int, seconds: float, side: str) -> dict:
    """One run of the program or of the control on the seed."""
    import time

    from bench import harness

    res, info = harness.execute(cell, seed, seconds, False,
                                time.perf_counter(),
                                control=side == "control")
    return {"seed": seed, "side": side, "correct": res["correct"],
            **{k: v["value"] for k, v in res["checks"].items()},
            "setup_s": res["metrics"]["setup_s"]["value"],
            "check_s": info.get("check_s")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="+")
    ap.add_argument("--seconds", type=float, default=2.0,
                    help="window of each run: long enough for the mix's "
                         "longest requests")
    args = ap.parse_args(argv)

    from bench import harness, spec

    cell = spec.cell(args.workload)
    try:
        harness.tpu_devices(cell.chips)
    except harness.NoAccelerator as e:
        print(f"bench.control: {e}", file=sys.stderr)
        return 3
    harness.enable_cache()
    runs = [(s, "program") for s in args.seeds] + [
        (s, "control") for s in (args.control_seeds or args.seeds)]
    for seed, side in runs:
        print(json.dumps(reading(cell, seed, args.seconds, side)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
