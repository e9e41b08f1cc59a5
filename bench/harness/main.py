"""One run of one cell: set-up, the measured window, the per-layer
readings (``--trace 1``), the comparison with the plain reference, and
the result's JSON line, last on standard output.

The compared numbers and their limits are also printed as the last lines
of standard error, and under ``checks``, the last key of the JSON line.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import tempfile
import time

from .cells import BENCH, ROOT, check_pieces, load_cell

PEAKS_FILE = BENCH / "harness" / "peaks.json"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def peak_for(device_kind: str) -> dict:
    """The peaks of ``device_kind`` from the table; an unknown kind is an
    error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


class Run:
    """What a per-layer reader may look at, for one run."""

    def __init__(self, cell, driver, trace, planes, window_s, peak):
        self.cell, self.config = cell, cell.config
        self.driver = cell.traffic["driver"]
        self.trace, self.planes = trace, planes
        self.window_s, self.peak = window_s, peak
        self.chips = cell.chips
        self.counters = driver.counters()


class GcPauses:
    """Count and length of the collector's passes over the oldest
    generation while active."""

    def __init__(self):
        self.count, self.seconds, self._t0 = 0, 0.0, None
        gc.callbacks.append(self._callback)

    def _callback(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.count += 1
            self.seconds += time.perf_counter() - self._t0
            self._t0 = None

    def close(self):
        gc.callbacks.remove(self._callback)


class CompileCount:
    """Backend compilations, and programs loaded from the persistent
    compile cache, while active, from JAX's monitoring events."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.compiled = self.loaded = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, **_):
        if self.on and event == self.COMPILE:
            self.compiled += 1

    def _event(self, event, **_):
        if self.on and event == self.CACHE_HIT:
            self.loaded += 1


def _traced_window(driver, seconds):
    import jax
    from .trace import WINDOW_SPAN, read_xplane
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                out = driver.window(seconds)
        finally:
            jax.profiler.stop_trace()
        return out, read_xplane(tmp)


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        devices, peak: dict, precision: dict | None = None) -> dict:
    """Set up, measure, read, compare; the result as a dict."""
    devices = devices[:cell.chips]
    driver = cell.driver.Driver(cell, seed, devices, precision)
    driver.setup()
    compiles = CompileCount()
    setup_s = time.perf_counter() - t_start
    compiles.on = True
    pauses = GcPauses()
    if trace:
        out, tr = _traced_window(driver, seconds)
    else:
        out, tr = driver.window(seconds), None
    compiles.on = False
    pauses.close()
    log(f"window: {out}; inside it {compiles.compiled} programs compiled, "
        f"{compiles.loaded} loaded from the compile cache, "
        f"{pauses.count} full collections took {pauses.seconds:.3f} s")

    planes = []
    if tr is not None:
        planes = [f"/device:TPU:{d.id}" for d in devices
                  if f"/device:TPU:{d.id}" in tr.devices]
        if not planes:                 # no device in the trace
            tr = None
    state = Run(cell, driver, tr, planes,
                tr.window_s if tr is not None else None, peak)
    if "generator_late_ms" in state.counters:
        log(f"generator ran late by (ms): "
            f"{state.counters['generator_late_ms']}")
    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)

    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = m.read(state)
            if value is not None:
                metrics[m.name] = {"value": float(value), "unit": m.unit}
    else:
        out = dict(out, setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m.name] = {"value": float(out[m.name]), "unit": m.unit}

    t_check = time.perf_counter()
    numbers = driver.check()
    log(f"reference comparison took {time.perf_counter() - t_check:.3f} s")
    limits = cell.limits["checks"]
    checks = {name: {"value": numbers[name], "limit": limits[name]["limit"]}
              for name in limits if name in numbers}
    missing = [name for name in limits if name not in numbers]
    correct = (not missing and out["failed"] == 0 and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values()))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if tr is not None:
        from .trace import idle_gaps, mean_busy_s, top_ops
        device.update(busy_s=mean_busy_s(tr, planes), window_s=tr.window_s)
        result["breakdown"] = {"device_ops": top_ops(tr, planes[0]),
                               "idle_gaps": idle_gaps(tr, planes[0])}
    result["checks"] = checks
    return result


def prepare(workload: str, root=ROOT):
    """Checks made before any work, then the compile cache: ``(cell,
    devices, peak)``. Exits non-zero where a piece the cell names is
    missing or lacks its interface, where the kernel is unknown, without a
    TPU, with fewer chips than the cell asks for, with x64 on, or for a
    device kind with no peaks."""
    # the benchmark's compile cache, at a fixed place in its checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    import jax
    cell = load_cell(workload, root)
    sys.path.insert(0, str(root / "src"))
    from .drivers import kernel_for
    kernel_for(cell.config)
    check_pieces(cell)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU: JAX's first device is "
                         f"{devices[0].platform!r}; the benchmark never "
                         f"runs on another platform")
    if len(devices) < cell.chips:
        raise SystemExit(f"bench: {workload} needs {cell.chips} chips, "
                         f"JAX sees {len(devices)}")
    if jax.config.jax_enable_x64:
        raise SystemExit("bench: x64 is on; the configurations are float32")
    peak = peak_for(devices[0].device_kind)

    from repro.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"jax {jax.__version__}; {devices[0].device_kind}; "
        f"{len(devices)} device(s); compile cache {enable_compile_cache()}")
    return cell, devices, peak


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell, devices, peak = prepare(args.workload)
    result = run(cell, args.seed, args.seconds, bool(args.trace), t_start,
                 devices, peak)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']:.6g} (limit {c['limit']:.6g})")
    print(json.dumps(result), flush=True)
    return 0
