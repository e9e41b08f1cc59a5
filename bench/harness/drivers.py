"""What the drivers share. A traffic mix names its driver by its
``driver`` key, ``bench/drivers/<driver>.py``, whose ``Driver`` class has
``setup()``, ``window(seconds)``, ``counters()`` and ``check()``:
``check`` runs after the window, frees the program's state and returns
each compared number, computed against the configuration's reference.

The drivers and this module are the only ones of the benchmark that
import the program.
"""
from __future__ import annotations

import dataclasses
import gc
import threading
import time

import numpy as np

from .data import seed31


def _kernel_classes() -> dict:
    """Each kernel class of the program, by its registry name and by its
    class name without ``Kernel`` (``poly`` and ``polynomial``)."""
    from repro.core.kernels import KERNELS
    names = dict(KERNELS)
    for cls in KERNELS.values():
        names[cls.__name__.removesuffix("Kernel").lower()] = cls
    return names


def kernel_for(config: dict):
    """The program's kernel that ``config["kernel"]`` names, with each of
    its parameters read from the configuration key of the same name
    (``bandwidth``; ``degree``, ``scale``, ``offset``; ...)."""
    classes = _kernel_classes()
    cls = classes.get(config["kernel"])
    if cls is None:
        raise SystemExit(f"unknown kernel {config['kernel']!r}; known: "
                         f"{sorted(classes)}")
    return cls(**{f.name: config[f.name] for f in dataclasses.fields(cls)
                  if f.name in config})


def sketch_config(config: dict, seed: int, precision: dict | None = None,
                  devices=None):
    """The ``SketchConfig`` a configuration file describes. A ``sharded``
    backend gets its mesh over ``devices``, the chips the cell asks for."""
    from repro.api import SketchConfig
    from repro.core.precision import Precision
    kw = dict(config.get("options", {}))
    if precision:
        kw["precision"] = Precision(**precision)
    if config["backend"] == "sharded" and devices is not None:
        # the program's data mesh spans JAX's first mesh_shape devices,
        # which are the cell's: the harness hands it devices[:chips]
        kw["mesh_shape"] = len(devices)
    return SketchConfig(
        kernel=kernel_for(config), p=config["p"],
        p_scores=config["p_scores"], lam=config["lam"], eps=config["eps"],
        seed=seed31(seed), sampler=config["sampler"],
        solver=config["solver"], backend=config["backend"], **kw)


def settle():
    """End of set-up: what set-up left behind is collected once and then
    frozen out of the collector's later passes, as a long-running server
    process would do after warm-up. Without it a full collection walks
    every object JAX made at import and compile time, and stalls the
    window's host threads for tens of milliseconds at a time."""
    gc.collect()
    gc.freeze()


class CellDriver:
    """A driver's seeded rows, sketch configuration and reference."""

    def __init__(self, cell, seed: int, devices, precision=None):
        self.cell, self.seed, self.devices = cell, seed, devices
        self.config, self.traffic = cell.config, cell.traffic
        self.ref = cell.reference
        self.cfg = sketch_config(self.config, seed, precision, devices)

    def _data(self):
        self.X, self.y, self.Xt, _ = self.cell.rows.make(self.config,
                                                         self.seed)

    def _reference_parts(self, idx, sketch_seed: int):
        """Reference scores and held-out predictions for the fit with
        ``SketchConfig.seed = sketch_seed`` whose Theorem-3 draw was
        ``idx``; the driver's own hold on the training rows is let go."""
        X, y, Xt = self.X, self.y, self.Xt
        self.X = self.y = None
        return self.ref.scores_and_predictions(self.config, sketch_seed,
                                               X, y, Xt, idx)


class Answers:
    """What the requests' done-callbacks record: each answer and the
    moment it was set, by request number. The futures themselves are not
    kept, so a resolved request leaves nothing alive behind it."""

    def __init__(self, n: int):
        self.n = n
        self.done = np.full(n, np.nan)
        self.values = np.full(n, np.nan)
        self.all_settled = threading.Event()
        self._settled = 0
        self._lock = threading.Lock()

    def record(self, i: int, future) -> None:
        t = time.perf_counter()
        try:
            self.values[i] = future.result().y_hat
            self.done[i] = t
        except Exception:            # noqa: BLE001 — a failed request
            pass                     # stays unanswered and is counted so
        with self._lock:
            self._settled += 1
            last = self._settled == self.n
        if last:
            self.all_settled.set()
