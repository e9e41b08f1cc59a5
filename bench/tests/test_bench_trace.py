"""The reduction from a profiler trace to the per-layer numbers: on a
trace small enough to count by hand, and on a trace recorded on one TPU
v5e (two closed-form sketched fits at n = 2^19, d = 28, p = 1024, cut to
the events the reduction reads), where the parts must add up."""
import json
from pathlib import Path

import pytest

from harness import trace as T

PLANE = "/device:TPU:0"
RECORDED = Path(__file__).with_name("data") / "trace_closed_form_fit.json"


@pytest.fixture
def by_hand():
    ops = [(100, 200, "fusion.1"), (150, 250, "all-reduce.3"),
           (400, 450, "fusion.2"), (900, 1200, "copy.7")]
    host = [(0, 1000, T.WINDOW_SPAN), (250, 420, "bench.fit"),
            (260, 300, "PjitFunction(solve)"), (460, 1000, "bench.fit")]
    return T.Trace((0, 1000), {PLANE: {"ops": ops}},
                   host)


def test_busy_and_idle_by_hand(by_hand):
    # union inside the window: [100, 250] + [400, 450] + [900, 1000]
    assert T.busy_ns(by_hand, PLANE) == 150 + 50 + 100
    assert T.mean_busy_s(by_hand, [PLANE]) == pytest.approx(300e-9)
    assert by_hand.window_s == pytest.approx(1000e-9)


def test_breakdown_by_hand(by_hand):
    assert T.top_ops(by_hand, PLANE) == [
        ["fusion", 150e-9], ["all-reduce", 100e-9], ["copy", 100e-9]]
    # gaps: [0,100] idle, [250,400] under PjitFunction? mid 325 -> bench.fit,
    # [450,900] mid 675 -> bench.fit
    assert T.idle_gaps(by_hand, PLANE) == [
        ["bench.fit", 450e-9], ["bench.fit", 150e-9], ["idle", 100e-9]]


@pytest.fixture(scope="module")
def recorded():
    data = json.loads(RECORDED.read_text())
    return T.Trace(tuple(data["window"]),
                   {p: {k: [tuple(e) for e in v] for k, v in d.items()}
                    for p, d in data["devices"].items()},
                   [tuple(h) for h in data["host"]])


def test_recorded_trace_adds_up(recorded):
    planes = sorted(recorded.devices)
    assert planes == [PLANE]
    lo, hi = recorded.window
    busy = T.busy_ns(recorded, PLANE)
    gaps = T.idle_gaps(recorded, PLANE, k=10**9)
    assert 0 < busy < hi - lo
    assert busy + sum(g[1] for g in gaps) * 1e9 == pytest.approx(hi - lo,
                                                                 abs=len(gaps))
    ops = T.clip(recorded.devices[PLANE]["ops"], recorded.window)
    assert busy <= sum(e - s for s, e, _ in ops)
    top = T.top_ops(recorded, PLANE, k=10**9)
    assert sum(t for _, t in top) * 1e9 == pytest.approx(
        sum(e - s for s, e, _ in ops), abs=len(top))
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)
