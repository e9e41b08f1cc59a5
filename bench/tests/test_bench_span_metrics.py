"""The readers of the program's serve spans, on a trace small enough to
count by hand: a 1 ms window holding two whole batches, with one batch
across each of its edges."""
import json
from types import SimpleNamespace

import pytest

from harness import trace as T
from harness.cells import ROOT, metric_module

US = 1000                                # ns


def _batch(start, pad, upload, predict, respond):
    """A ``serve.batch`` span at ``start`` (µs) and its four children, of
    the given lengths (µs), back to back after 2 µs of its own."""
    spans, t = [], start + 2
    for name, length in (("serve.pad", pad), ("serve.upload", upload),
                         ("serve.predict", predict),
                         ("serve.respond", respond)):
        spans.append((t * US, (t + length) * US, name))
        t += length
    return [(start * US, (t + 3) * US, "serve.batch")] + spans


@pytest.fixture
def by_hand():
    host = [(1000 * US, 2000 * US, T.WINDOW_SPAN)]
    host += _batch(940, 10, 10, 50, 10)      # 940-1025: across the start
    host += [(1025 * US, 1100 * US, "serve.wait")]
    host += _batch(1100, 10, 30, 100, 40)    # 1100-1285
    host += [(1150 * US, 1240 * US, "PjitFunction(serve_predict)")]
    host += [(1285 * US, 1500 * US, "serve.wait")]
    host += _batch(1500, 5, 15, 60, 10)      # 1500-1595
    host += [(1595 * US, 1950 * US, "serve.wait")]
    host += _batch(1950, 10, 20, 70, 20)     # 1950-2075: across the end
    return T.Trace((1000 * US, 2000 * US), {}, host)


# batches cut to the window: 25 + 185 + 95 + 50 µs of 1000; the two whole
# ones last 185 and 95 µs, with children 10+30, 100, 40 and 5+15, 60, 10
BY_HAND = {
    "worker_busy_share.serve": 100.0 * (25 + 185 + 95 + 50) / 1000,
    "serve_batch_ms": (185 + 95) / 2 / 1000,
    "serve_pad_upload_ms": (10 + 30 + 5 + 15) / 2 / 1000,
    "serve_predict_ms": (100 + 60) / 2 / 1000,
    "serve_respond_ms": (40 + 10) / 2 / 1000,
}


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_value_by_hand(name, by_hand):
    read = metric_module(name).read
    assert read(SimpleNamespace(trace=by_hand)) == pytest.approx(
        BY_HAND[name])


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_silent_without_a_trace(name):
    assert metric_module(name).read(SimpleNamespace(trace=None)) is None


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_silent_without_serve_batch_spans(name, by_hand):
    """A program without the spans, as the parent of the change that
    added them: the reader finds nothing and returns None."""
    host = [h for h in by_hand.host if not h[2].startswith("serve.")]
    tr = T.Trace(by_hand.window, {}, host)
    assert metric_module(name).read(SimpleNamespace(trace=tr)) is None


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_registered_as_a_serve_span_metric(name):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in spec["per_layer"] if m["name"] == name)
    assert entry["source"] == "program_span" and entry["layer"] == "serve"
    assert entry["workloads"] == ["susy_falkon.serve_poisson"]


def test_parts_of_a_batch_add_up_to_at_most_the_batch(by_hand):
    run = SimpleNamespace(trace=by_hand)
    parts = sum(metric_module(n).read(run) for n in (
        "serve_pad_upload_ms", "serve_predict_ms", "serve_respond_ms"))
    assert parts <= metric_module("serve_batch_ms").read(run)
