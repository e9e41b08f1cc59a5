"""Every cell's path at a tiny size on the CPU, through the harness's own
functions: set-up, window, per-layer readers, reference comparison and
the result line."""
import pytest

from bench_tiny import run_tiny
from harness.cells import load_cell

ONE_CHIP = ["susy_falkon.serve_poisson", "susy_falkon.fit"]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_cell_runs_and_compares(workload):
    res = run_tiny(workload)
    assert list(res) == KEYS          # ``checks`` comes last
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    cell = load_cell(workload)
    assert set(res["metrics"]) == {m.name for m in cell.end_to_end}
    assert set(res["checks"]) == set(cell.limits["checks"])
    assert res["device"]["platform"] == "cpu"


# the per-layer metrics read from the program's counters alone
COUNTERS = {"susy_falkon.serve_poisson": {"serve_batch_rows", "serve_p99_ms"},
            "susy_falkon.fit": set()}


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_traced_run_reports_counters_only_off_chip(workload):
    """Off the chip the trace has no device plane: the device and span
    readers stay silent, the program's counters still read."""
    res = run_tiny(workload, trace=True)
    assert res["correct"]
    assert "breakdown" not in res and "busy_s" not in res["device"]
    assert set(res["metrics"]) == COUNTERS[workload]
