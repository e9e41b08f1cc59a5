#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload susy_falkon.serve_poisson --seed 7 \
        --seconds 20 --trace 0

The cell is found by name in ``BENCHMARK.json``; its configuration,
traffic mix, driver, rows, reference, limits and per-layer readers in
the files under ``bench/`` that carry those names. The last line of standard output is the result's
JSON object. The run exits non-zero, printing no result, unless JAX's
first device is a TPU and there are as many as the cell asks for.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness.main import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
