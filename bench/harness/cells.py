"""Find a cell's pieces by name: its workload entry in ``BENCHMARK.json``,
its configuration file, its traffic mix, its correctness limits, the
driver its traffic names, the rows and reference its configuration
names, and the readers of its per-layer metrics.

Every piece lives in a file of its own, so a new configuration, mix or
metric is added by adding files and entries in ``BENCHMARK.json``, with
no edit to a file that is there:

    bench/configs/<config>.json      sizes, kernel, rows, reference, source
    bench/rows/<rows>.py             ``make(config, seed) -> (X, y, Xt, yt)``
    <config["reference"]>            the plain reference (``REFERENCE_API``)
    bench/traffic/<traffic>.json     parameters of the driver it names
    bench/drivers/<driver>.py        ``Driver`` (``DRIVER_API``)
    bench/limits/<workload>.json     each compared number and its limit
    bench/metrics/<metric>.py        ``read(run) -> float | None``

``load_cell`` loads each file a cell names and stops on one that is
missing; ``check_pieces`` stops on a module that lacks its interface.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

DRIVER_API = ("setup", "window", "counters", "check")
ROWS_API = ("make",)
# Theorem-4 scores and held-out predictions of a fit, given its sketch
# seed and its Theorem-3 draw; the two comparisons; the kernels it covers
REFERENCE_API = ("scores_and_predictions", "rel_rms", "max_gap", "KERNELS")


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
    driver: object             # module bench/drivers/<traffic["driver"]>.py
    rows: object               # module bench/rows/<config["rows"]>.py
    reference: object          # module at config["reference"]


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    """A metric with a ``workloads`` list is read in those cells; one
    without (``setup_s``) in every cell."""
    return workload in metric.get("workloads", [workload])


@functools.lru_cache(maxsize=None)
def load_module(path: Path):
    """The module in the file ``path``, executed once per process."""
    name = "bench_" + re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _piece(workload: str, kind: str, path: Path):
    if not path.is_file():
        raise SystemExit(f"{workload}: its {kind} names {path}, which is "
                         f"not there")
    return load_module(path.resolve())


def metric_module(name: str, root: Path = ROOT, workload: str = "bench"):
    """The reader ``bench/metrics/<name>.py`` (a name may hold dots)."""
    return _piece(workload, f"metric {name}",
                  root / "bench" / "metrics" / f"{name}.py")


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
    traffic = _load_json(root / "bench" / "traffic"
                         / f"{entry['traffic']}.json")
    limits = _load_json(root / "bench" / "limits" / f"{workload}.json")
    reference = (root / config["reference"]).resolve()
    if (root / "bench").resolve() not in reference.parents:
        raise SystemExit(f"{workload}: reference {config['reference']} lies "
                         f"outside bench/")
    e2e = tuple(Metric(m["name"], m["unit"], m["source"])
                for m in bench["end_to_end"] if _applies(m, workload))
    per_layer = tuple(Metric(m["name"], m["unit"], m["source"],
                             metric_module(m["name"], root, workload).read)
                      for m in bench["per_layer"] if _applies(m, workload))
    return Cell(
        workload, int(entry["chips"]), config, traffic, limits, e2e,
        per_layer,
        driver=_piece(workload, "traffic's driver", root / "bench"
                      / "drivers" / f"{traffic['driver']}.py"),
        rows=_piece(workload, "configuration's rows",
                    root / "bench" / "rows" / f"{config['rows']}.py"),
        reference=_piece(workload, "configuration's reference", reference))


def check_pieces(cell: Cell) -> None:
    """Stop unless each module the cell loaded has its interface, and the
    reference covers the configuration's kernel."""
    driver = getattr(cell.driver, "Driver", None)
    for module, obj, names in ((cell.driver, cell.driver, ("Driver",)),
                               (cell.driver, driver, DRIVER_API),
                               (cell.rows, cell.rows, ROWS_API),
                               (cell.reference, cell.reference,
                                REFERENCE_API)):
        missing = [n for n in names if not hasattr(obj, n)]
        if missing:
            raise SystemExit(f"{cell.name}: {module.__file__} lacks "
                             f"{', '.join(missing)}")
    if cell.config["kernel"] not in cell.reference.KERNELS:
        raise SystemExit(f"{cell.name}: reference {cell.config['reference']}"
                         f" covers kernels {list(cell.reference.KERNELS)}, "
                         f"not {cell.config['kernel']!r}")
