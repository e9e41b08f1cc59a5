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

from harness.cells import BENCH, ROOT, check_pieces, load_cell
from harness.data import fit_seed, seed31
from harness.drivers import kernel_for
from harness.main import peak_for, prepare

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


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_every_config_names_pieces_that_resolve(config):
    """Its rows, its reference and its kernel, each found and whole."""
    workload = next(w["name"] for w in SPEC["workloads"]
                    if w["config"] == config)
    cell = load_cell(workload)
    check_pieces(cell)
    assert callable(cell.rows.make)
    assert cell.config["kernel"] in cell.reference.KERNELS
    assert type(kernel_for(cell.config)).__name__ == "RBFKernel"
    assert kernel_for(cell.config).bandwidth == cell.config["bandwidth"]


def test_linear_and_polynomial_kernels_are_the_programs():
    from repro.core.kernels import LinearKernel, PolynomialKernel
    assert kernel_for({"kernel": "linear"}) == LinearKernel()
    poly = kernel_for({"kernel": "polynomial", "degree": 3, "offset": 0.5})
    assert poly == PolynomialKernel(degree=3, offset=0.5)
    assert kernel_for({"kernel": "poly", "degree": 3, "offset": 0.5}) == poly


@pytest.fixture
def copy(tmp_path, monkeypatch):
    """``BENCHMARK.json`` and ``bench/`` copied, to break one piece in;
    what ``prepare`` sets in the process is put back afterwards."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(sys, "path", list(sys.path))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def _edit_json(path, **changes):
    data = json.loads(path.read_text())
    data.update(changes)
    path.write_text(json.dumps(data))


BROKEN = {
    "driver": (lambda root: _edit_json(
        root / "bench/traffic/serve_poisson.json", driver="no_such_driver"),
        "no_such_driver.py, which is not there"),
    "rows": (lambda root: _edit_json(
        root / "bench/configs/susy_falkon.json", rows="no_such_rows"),
        "no_such_rows.py, which is not there"),
    "reference": (lambda root: _edit_json(
        root / "bench/configs/susy_falkon.json",
        reference="bench/harness/no_such_reference.py"),
        "no_such_reference.py, which is not there"),
    "reference_interface": (lambda root: (
        (root / "bench/harness/bare.py").write_text("KERNELS = ('rbf',)\n"),
        _edit_json(root / "bench/configs/susy_falkon.json",
                   reference="bench/harness/bare.py")),
        "lacks scores_and_predictions, rel_rms, max_gap"),
    "driver_interface": (lambda root: (
        root / "bench/drivers/serve_poisson.py").write_text(
            "class Driver:\n    def setup(self):\n        pass\n"),
        "lacks window, counters, check"),
    "kernel": (lambda root: _edit_json(
        root / "bench/configs/susy_falkon.json", kernel="no_such_kernel"),
        "unknown kernel 'no_such_kernel'; known: \\['bernoulli', "),
    "kernel_of_reference": (lambda root: _edit_json(
        root / "bench/configs/susy_falkon.json", kernel="linear"),
        "covers kernels \\['rbf'\\], not 'linear'"),
}


@pytest.mark.parametrize("piece", sorted(BROKEN))
def test_broken_piece_stops_prepare_before_any_work(piece, copy,
                                                    monkeypatch):
    """``prepare`` stops on the piece, before it looks for a chip (which
    the CPU would fail) and before any driver, rows or fit is made."""
    break_it, message = BROKEN[piece]
    break_it(copy)
    made = []
    monkeypatch.setattr(
        "harness.drivers.CellDriver.__init__",
        lambda *a, **k: made.append(a))
    with pytest.raises(SystemExit, match=message):
        prepare("susy_falkon.serve_poisson", root=copy)
    assert not made


def test_sound_pieces_reach_the_look_for_a_chip(copy):
    with pytest.raises(SystemExit, match="no TPU"):
        prepare("susy_falkon.serve_poisson", root=copy)


def test_fit_seeds_differ_by_fit_and_by_run():
    runs = (0, 5, 2**32 + 5, 2**33 + 5)
    seeds = {fit_seed(s, i) for s in runs for i in range(4)}
    assert len(seeds) == 16 and all(0 <= s < 2**31 for s in seeds)
    assert fit_seed(5, 1) == fit_seed(5, 1)


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
