"""The comparison that decides ``correct`` fails what it must: each cell's
control (the program at the lower precision its limits file names) and
each fault the cell can have (``tools/faults.py``), planted under a tiny
run of the harness."""
import pytest

import faults
from bench_tiny import CONTROL_SIZE, run_tiny
from harness.cells import load_cell


@pytest.mark.parametrize("workload", ["susy_falkon.serve_poisson",
                                      "susy_falkon.fit"])
def test_control_is_not_correct(workload):
    """The program's bf16 data path (bf16 rows and kernel blocks, float32
    p x p solves) runs to its end and fails a limit, while the program at
    the same size passes."""
    control = load_cell(workload).limits["control"]["precision"]
    assert run_tiny(workload, size=CONTROL_SIZE)["correct"]
    res = run_tiny(workload, precision=control, size=CONTROL_SIZE)
    assert res["failed"] == 0
    assert not res["correct"], res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_one_served_answer_altered_is_not_correct(monkeypatch):
    faults.plant_altered_answer(monkeypatch.setattr)
    res = run_tiny("susy_falkon.serve_poisson")
    assert not res["correct"], res["checks"]
    gap = res["checks"]["served_max_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("fault, fails", [
    ("altered_prediction", "pred_max_gap"),
    ("pcg_step_unchanged", "pred_rel_rms"),
    ("half_rows", "pred_rel_rms"),
])
def test_fit_fault_is_not_correct(fault, fails, monkeypatch):
    getattr(faults, f"plant_{fault}")(monkeypatch.setattr)
    res = run_tiny("susy_falkon.fit")
    assert not res["correct"], res["checks"]
    check = res["checks"][fails]
    assert check["value"] > check["limit"], res["checks"]
