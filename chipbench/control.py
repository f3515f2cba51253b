"""Readings for the limits of a cell's check, many seeds in one process.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 --seconds <s>

For each seed it runs the cell as a benchmark run does (set-up, warm-up,
a window of ``--seconds``, the check against the reference) and prints
one JSON line with the readings of every compared number:

* ``program``: the program against the reference -- the lower reading;
* ``control`` and any planted faults: each against the reference -- the
  upper readings (``control_readings`` of the cell's kind says what each
  one is).

The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import importlib  # noqa: E402

from chipbench import harness  # noqa: E402


def values(checks: dict) -> dict:
    return {k: c["value"] for k, c in checks.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--require-chip", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            res = harness.run_cell(args.workload, seed, args.seconds, False,
                                   t_start=time.perf_counter(),
                                   require_chip=bool(args.require_chip))
        except harness.Refused as e:
            harness.log(f"refused: {e}")
            return 2
        cell = harness.load_cell(args.workload)
        kind = importlib.import_module(
            f"chipbench.kinds.{cell.config['kind']}")
        upper = kind.control_readings(cell, seed, res)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "iterations": res["iterations"], "correct": res["correct"],
            "program": values(res["checks"]),
            **{k: values(v) for k, v in upper.items()},
            "limits": {k: c["limit"] for k, c in res["checks"].items()},
            "device": res["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
