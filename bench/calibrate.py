#!/usr/bin/env python3
"""Readings behind a cell's limits: the program's numbers and the control's,
over several seeds in one process (one set-up per seed, programs compiled
once).

    python3 bench/calibrate.py --workload e3sm-compress --seeds 11,12,13 --seconds 8

Prints one JSON line per seed: the program's numbers, the control's, and
whether each run was correct.  The benchmark's own runs never run the
control; this is how its limits were read (PERF.md).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import run

    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        result = run.run_cell(args.workload, seed, args.seconds, False,
                              control=True, t_start=t0)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": result["correct"],
            "numbers": {k: v["value"] for k, v in result["checks"].items()},
            "control_correct": result["control"]["correct"],
            "control": result["control"]["numbers"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "attempted": result["attempted"],
            "setup_parts": result["setup_parts"],
            "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
