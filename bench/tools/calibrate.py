#!/usr/bin/env python3
"""Readings behind a cell's correctness limits: the numbers the run
compares, for the program and for its control, over many seeds, in one
process on the chip.

    python3 bench/tools/calibrate.py --workload susy_falkon.serve_poisson \
        --seeds 101-112 --control-seeds 201-203 --seconds 3

The control is the program with the lower precision that the cell's
limits file names under ``control`` (``Precision`` fields), or the one
``--precision`` gives as JSON. ``--fault <name> [args]`` plants one of
``tools/faults.py``'s faults under every run. Each seed runs the cell's
own driver: set-up, a window of ``--seconds`` at the cell's load, then
the comparison. One JSON line per seed on
standard output.
"""
import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parent)]

from harness.main import log, prepare  # noqa: E402


def seed_range(text: str) -> list:
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seed_range, default=[])
    ap.add_argument("--control-seeds", type=seed_range, default=[])
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--precision", type=json.loads, default=None)
    ap.add_argument("--fault", nargs="+", default=None)
    args = ap.parse_args()
    cell, devices, _ = prepare(args.workload)
    control = args.precision or cell.limits["control"]["precision"]
    if args.fault:
        import faults
        getattr(faults, f"plant_{args.fault[0]}")(setattr, *args.fault[1:])
    runs = [(s, None) for s in args.seeds] + \
        [(s, control) for s in args.control_seeds]
    for seed, precision in runs:
        driver = cell.driver.Driver(cell, seed, devices[:cell.chips],
                                    precision)
        out, numbers = {}, {}
        try:
            driver.setup()
            out = driver.window(args.seconds)
            numbers = driver.check()
        except Exception as exc:              # noqa: BLE001 — reported
            numbers = {"error": repr(exc)}
        line = {"workload": cell.name, "seed": seed, "fault": args.fault,
                "control": precision, "window": out, "numbers": numbers}
        print(json.dumps(line), flush=True)
        del driver
    log("calibrate: done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
