"""Find a cell's pieces by name: its workload entry in ``BENCHMARK.json``,
its configuration file, its traffic mix, its correctness limits and the
readers of its per-layer metrics.

Every piece lives in a file of its own, so a new configuration, mix or
metric is added by adding a file and an entry in ``BENCHMARK.json``:

    bench/configs/<config>.json      sizes, source, reduced, assumed
    bench/traffic/<traffic>.json     parameters of one general driver
    bench/limits/<workload>.json     each compared number and its limit
    bench/metrics/<metric>.py        ``read(run) -> float | None``
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    source: str
    read: object = None        # per-layer reader, None for end-to-end


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple
    per_layer: tuple


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    """A metric with a ``workloads`` list is read in those cells; one
    without (``setup_s``) in every cell."""
    return workload in metric.get("workloads", [workload])


def metric_module(name: str):
    """The module ``bench/metrics/<name>.py`` (a name may hold dots)."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload`` with every file it names loaded."""
    bench = _load_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"known: {[w['name'] for w in bench['workloads']]}")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    config = _load_json(root / cfg_entry["file"])
    traffic = _load_json(BENCH / "traffic" / f"{entry['traffic']}.json")
    limits = _load_json(BENCH / "limits" / f"{workload}.json")
    e2e = tuple(Metric(m["name"], m["unit"], m["source"])
                for m in bench["end_to_end"] if _applies(m, workload))
    per_layer = tuple(Metric(m["name"], m["unit"], m["source"],
                             metric_module(m["name"]).read)
                      for m in bench["per_layer"] if _applies(m, workload))
    return Cell(workload, int(entry["chips"]), config, traffic, limits,
                e2e, per_layer)
