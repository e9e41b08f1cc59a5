"""Reduction of a ``jax.profiler`` trace to the numbers the per-layer
metrics read.

A trace is first flattened to plain tuples, so that the arithmetic below
runs the same on a trace read from the chip and on a small recorded one
in the tests:

    Trace(window=(start_ns, end_ns),
          devices={plane: {"ops": [...], "modules": [...]}},
          host=[...])

where every list holds ``(start_ns, end_ns, name)``. ``window`` is the
benchmark's own ``bench.window`` span on the host; device events are
clipped to it.

On a TPU the profiler writes one plane per chip, ``/device:TPU:<i>``,
whose ``XLA Ops`` line holds every operation the chip ran, and whose
``XLA Modules`` line every run of a whole program, named by the program
(``jit_score_pass(<id>)``).
"""
from __future__ import annotations

import dataclasses
import glob
import re

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    window: tuple            # (start_ns, end_ns) of the measured window
    devices: dict            # plane -> {"ops": [...], "modules": [...]}
    host: list               # (start_ns, end_ns, name) on host threads

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def read_xplane(log_dir: str) -> Trace:
    """The ``Trace`` of the one ``.xplane.pb`` under ``log_dir``."""
    import jax
    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    devices, host, window = {}, [], None
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            devices[plane.name] = {
                key: [(int(e.start_ns), int(e.end_ns), e.name)
                      for e in (lines[line].events if line in lines
                                else [])]
                for key, line in (("ops", OPS_LINE),
                                  ("modules", MODULES_LINE))}
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    span = (int(e.start_ns), int(e.end_ns), e.name)
                    if e.name == WINDOW_SPAN:
                        window = span[:2]
                    host.append(span)
    if window is None:
        raise RuntimeError(f"no {WINDOW_SPAN!r} span in the trace")
    return Trace(window, devices, host)


def clip(events, window):
    """Events cut to the window; those outside it dropped."""
    lo, hi = window
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def whole_spans(trace: Trace, name: str) -> list:
    """Host spans named ``name`` that lie wholly inside the window."""
    lo, hi = trace.window
    return [h for h in trace.host if h[2] == name and lo <= h[0]
            and h[1] <= hi]


def within(events, outer) -> list:
    """The events that lie wholly inside the span ``outer``."""
    return [e for e in events if outer[0] <= e[0] and e[1] <= outer[1]]


def union(events) -> list:
    """Merged ``(start, end)`` intervals covered by any event."""
    merged = []
    for s, e, _ in sorted(events):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def program_runs(trace: Trace, planes, program: str) -> list:
    """Every run on ``planes`` of the program named ``program``
    (``jit_score_pass``, as ``jit_score_pass(<id>)`` or
    ``jit_score_pass.<n>``)."""
    return [m for p in planes for m in trace.devices[p].get("modules", [])
            if re.sub(r"\.\d+$", "", m[2].split("(")[0]) == program]


def busy_ns(trace: Trace, plane: str) -> int:
    """Nanoseconds of the window in which some operation ran on ``plane``."""
    return sum(e - s for s, e in
               union(clip(trace.devices[plane]["ops"], trace.window)))


def mean_busy_s(trace: Trace, planes) -> float:
    """Busy seconds averaged over ``planes`` (the chips the cell uses)."""
    return sum(busy_ns(trace, p) for p in planes) / len(planes) / 1e9


def op_name(hlo: str) -> str:
    """``copy f32[524288,1024]`` for an ``XLA Ops`` event named by its HLO
    text (``%copy.3 = f32[524288,1024]{0,1:T(8,128)} copy(...)``)."""
    m = re.match(r"%?([\w\-]+?)(?:\.\d+)? = (\(?[a-z0-9]+\[[\d,]*\])", hlo)
    if m is None:
        return re.sub(r"[.\d]+$", "", hlo) or hlo
    return f"{m.group(1)} {m.group(2).lstrip('(')}"


def top_ops(trace: Trace, plane: str, k: int = 10) -> list:
    """``[[name, seconds], ...]``: the ``k`` operations (by ``op_name``)
    that took the most device time."""
    total: dict = {}
    for s, e, n in clip(trace.devices[plane]["ops"], trace.window):
        key = op_name(n)
        total[key] = total.get(key, 0) + (e - s)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns / 1e9] for n, ns in ranked]


def idle_gaps(trace: Trace, plane: str, k: int = 10) -> list:
    """``[[host activity, seconds], ...]``: the ``k`` longest stretches of
    the window in which ``plane`` ran nothing, each named by the
    innermost host span around its middle (``idle`` where none is)."""
    busy = union(clip(trace.devices[plane]["ops"], trace.window))
    edges = [trace.window[0]] + [t for iv in busy for t in iv] \
        + [trace.window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    host = [h for h in trace.host if h[2] != WINDOW_SPAN]
    out = []
    for s, e in gaps:
        mid = (s + e) // 2
        around = [h for h in host if h[0] <= mid < h[1]]
        name = min(around, key=lambda h: h[1] - h[0])[2] if around \
            else "idle"
        out.append([name, (e - s) / 1e9])
    return out
