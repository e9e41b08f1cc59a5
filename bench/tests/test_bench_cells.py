"""``BENCHMARK.json`` and the files it names: every cell loads, every name
and key is one the contract allows, and the entry refuses to run without
a TPU or without the program beside it."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from harness.cells import BENCH, ROOT, load_cell
from harness.data import seed31
from harness.main import peak_for

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_and_references():
    configs = {c["name"] for c in SPEC["configs"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for entry in SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] \
            + SPEC["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
    for w in SPEC["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= set(WORKLOADS)
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) \
        <= max(1, len(WORKLOADS) // 2)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_loads_with_every_piece(workload):
    cell = load_cell(workload)
    names = {m.name for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer and all(callable(m.read) for m in cell.per_layer)
    assert cell.limits["checks"] and cell.limits["control"]["precision"]
    reduced = next(c["reduced"] for c in SPEC["configs"]
                   if c["name"] == cell.config["name"])
    assert set(reduced) == set(cell.config["reduced"])


def test_seed_of_any_size_maps_into_31_bits():
    seeds = [0, 1, 2**31 - 1, 2**31 + 12345, 2**40 + 3]
    mapped = [seed31(s) for s in seeds]
    assert all(0 <= m < 2**31 for m in mapped)
    assert len(set(mapped)) == len(seeds)
    assert mapped == [seed31(s) for s in seeds]


def test_unknown_device_kind_is_an_error():
    assert peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit, match="no peaks"):
        peak_for("cpu")


def _entry(cwd, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOADS[0],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


def test_entry_refuses_a_cpu():
    out = _entry(ROOT)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_entry_fails_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _entry(tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""
