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


# --- the fit's spans: a 10 ms window holding two whole fits, with one fit
# across its start; each fit a score pass, a draw and a solver.fit with
# its PCG steps nested inside, and on the chip each fit's score-pass
# program running on past its span into the solver's

def _fit(start, score, draw, pre, steps):
    """An ``estimator.fit`` span at ``start`` (µs): its score pass, draw
    and ``solver.fit``, which holds ``pre`` µs of its own and then the
    ``solver.pcg_step``s of the given lengths (µs)."""
    spans, t = [], start
    for name, length in (("sampler.score_pass", score),
                         ("sampler.draw", draw)):
        spans.append((t * US, (t + length) * US, name))
        t += length
    solver, t = t, t + pre
    for length in steps:
        spans.append((t * US, (t + length) * US, "solver.pcg_step"))
        t += length
    spans.append((solver * US, t * US, "solver.fit"))
    return [(start * US, t * US, "estimator.fit")] + spans


@pytest.fixture
def fits_by_hand():
    host = [(1000 * US, 11000 * US, T.WINDOW_SPAN)]
    host += _fit(600, 200, 50, 300, [90, 40, 40])         # 600-1320: across
    host += _fit(1400, 500, 100, 1000, [300, 100, 120, 100])
    host += _fit(4000, 400, 100, 800, [200, 110, 90])
    host += [(4100 * US, 4300 * US, "PjitFunction(score_pass)")]
    ops = [(1000 * US, 3000 * US, "fusion.1"), (2500 * US, 3500 * US,
                                                "fusion.2"),
           (10500 * US, 12000 * US, "copy.3")]
    modules = [(650 * US, 1100 * US, "jit_score_pass(7)"),   # across
               (1450 * US, 2400 * US, "jit_score_pass(7)"),
               (3000 * US, 3300 * US, "jit_pcg_step(9)"),
               (4050 * US, 4850 * US, "jit_score_pass(7)")]
    return T.Trace((1000 * US, 11000 * US),
                   {"/device:TPU:0": {"ops": ops, "modules": modules}}, host)


# two whole fits: score-pass programs on the chip until 2400 and 4850 µs,
# 400 (2000-2400) and 350 (4500-4850) of them inside solver.fit; solver.fit
# 1620 and 1200 µs, of which PCG steps 620 and 400; steps after each
# first 100, 120, 100 and 110, 90; busy 2500 + 500 µs of the 10,000
FIT_BY_HAND = {
    "fit_precond_s": (1000 - 400 + 800 - 350) / 2 / 1e6,
    "pcg_step_ms": 100 / 1000,
    "pcg_steps": (4 + 3) / 2,
    "device_idle_share.fit": 100.0 * (1 - 3000 / 10000),
}


def _fit_run(trace):
    return SimpleNamespace(trace=trace, planes=["/device:TPU:0"],
                           driver="fit_loop")


@pytest.mark.parametrize("name", sorted(FIT_BY_HAND))
def test_fit_value_by_hand(name, fits_by_hand):
    read = metric_module(name).read
    assert read(_fit_run(fits_by_hand)) == pytest.approx(FIT_BY_HAND[name])


@pytest.mark.parametrize("name", sorted(FIT_BY_HAND))
def test_fit_reader_silent_without_a_trace(name):
    assert metric_module(name).read(_fit_run(None)) is None


@pytest.mark.parametrize("name", sorted(set(FIT_BY_HAND)
                                        - {"device_idle_share.fit"}))
def test_fit_reader_silent_without_fit_spans(name, fits_by_hand):
    """No fit's spans on the host, and no score-pass program on the
    chip: nothing to read."""
    host = [h for h in fits_by_hand.host
            if not h[2].startswith(("sampler.", "solver."))]
    devices = {p: {"ops": d["ops"], "modules": [
        m for m in d["modules"] if not m[2].startswith("jit_score_pass")]}
        for p, d in fits_by_hand.devices.items()}
    tr = T.Trace(fits_by_hand.window, devices, host)
    assert metric_module(name).read(_fit_run(tr)) is None


def test_device_idle_share_reads_only_its_own_driver(fits_by_hand):
    run = _fit_run(fits_by_hand)
    run.driver = "serve_poisson"
    assert metric_module("device_idle_share.fit").read(run) is None
    run.driver = "fit_loop"
    assert metric_module("device_idle_share.serve").read(run) is None


@pytest.mark.parametrize("name", sorted(FIT_BY_HAND))
def test_registered_as_a_fit_metric(name):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in spec["per_layer"] if m["name"] == name)
    assert entry["moves"] == "fit_s"
    assert entry["workloads"] == ["susy_falkon.fit"]
    assert entry["source"] == ("device_trace" if name.startswith("device")
                               else "program_span")


def test_a_fit_is_its_parts(fits_by_hand):
    """Per whole fit, the PCG steps, the score pass's device time inside
    it and the solver's own time make up the solver.fit span."""
    run = _fit_run(fits_by_hand)
    steps = metric_module("pcg_steps").read(run)
    own = metric_module("fit_precond_s").read(run)
    whole = 1400 * US                    # the first whole fit's start
    solver = [(s, e) for s, e, n in fits_by_hand.host
              if n == "solver.fit" and s >= whole]
    pcg = [e - s for s, e, n in fits_by_hand.host
           if n == "solver.pcg_step" and s >= whole]
    waited = sum(e - s for a, b in solver for s, e, name in T.clip(
        fits_by_hand.devices["/device:TPU:0"]["modules"], (a, b))
        if name.startswith("jit_score_pass"))
    assert steps * len(solver) == len(pcg)
    assert own * 1e9 * len(solver) + sum(pcg) + waited == pytest.approx(
        sum(e - s for s, e in solver))
