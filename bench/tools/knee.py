#!/usr/bin/env python3
"""Sweep of offered rates for a serve cell, to find its knee: the highest
rate at which the p95 latency meets the traffic file's ``p95_limit_ms``
with no growing backlog. One process, one set-up, one window per rate.

    python3 bench/tools/knee.py --workload susy_falkon.serve_poisson \
        --rates 1000,2000,4000 --seconds 10 --seed 5

A backlog grows where the median latency of the last quarter of a
window's requests is more than twice that of the first quarter.
"""
import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness.main import log, prepare  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    cell, devices, _ = prepare(args.workload)
    driver = cell.driver.Driver(cell, args.seed, devices[:cell.chips])
    driver.setup()
    limit = cell.traffic["p95_limit_ms"]
    for rate in (float(r) for r in args.rates.split(",")):
        driver.traffic = dict(cell.traffic, rate_per_s=rate)
        out = driver.window(args.seconds)
        lat = driver.lat_ms
        q = max(1, len(lat) // 4)
        growth = float(np.median(lat[-q:]) / np.median(lat[:q]))
        sizes = driver.counters()["batch_sizes"]
        line = dict(out, rate_per_s=rate, backlog_growth=growth,
                    meets=out["serve_p95_ms"] <= limit and growth <= 2.0
                    and out["failed"] == 0,
                    mean_batch=float(np.mean(sizes)) if sizes else None,
                    window_s=driver.window_s,
                    generator_late_ms=driver.counters()["generator_late_ms"])
        driver.batches_before = len(driver.engine.stats().batch_sizes)
        print(json.dumps(line), flush=True)
    driver.engine.stop()
    log("knee: done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
