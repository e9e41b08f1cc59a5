"""Profiler spans at the layer boundaries of the serve worker and the fit.

Inside a ``jax.profiler`` session the async engine's worker records
``serve.wait`` around batch formation and ``serve.batch`` around each
served batch, with ``serve.pad``, ``serve.upload``, ``serve.predict``
and ``serve.respond`` nested in it; an in-memory fit records
``estimator.fit`` around ``sampler.score_pass``, ``sampler.draw`` and
``solver.fit``, and one ``solver.pcg_step`` per PCG iteration. These
tests serve and fit a tiny model on the CPU under the profiler and read
the spans back from the ``.xplane.pb`` it writes.
"""
from __future__ import annotations

import glob
from typing import NamedTuple

import jax
import numpy as np
import pytest

from repro.api import SketchConfig, SketchedKRR
from repro.core import RBFKernel
from repro.serve import AsyncServeEngine, BatchPolicy

PREFIXES = ("serve.", "estimator.", "sampler.", "solver.")
CHILDREN = ("serve.pad", "serve.upload", "serve.predict", "serve.respond")


class Span(NamedTuple):
    name: str
    start: int
    end: int
    meta: dict


def _fit(solver, n=512, d=4, p=32):
    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, d))
    y = np.sin(X[:, 0]) + 0.3 * X[:, 1]
    cfg = SketchConfig(kernel=RBFKernel(1.2), p=p, lam=1e-3, seed=3,
                       sampler="rls_fast", solver=solver, backend="xla")
    return SketchedKRR(cfg), X, y


def _traced(log_dir, work) -> list[Span]:
    """Run ``work()`` in a profiler session; the program's spans, by
    start time."""
    jax.profiler.start_trace(str(log_dir))
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    assert len(paths) == 1
    data = jax.profiler.ProfileData.from_file(paths[0])
    spans = [Span(e.name, int(e.start_ns), int(e.end_ns), dict(e.stats))
             for plane in data.planes if plane.name.startswith("/host:CPU")
             for line in plane.lines for e in line.events
             if e.name.startswith(PREFIXES)]
    return sorted(spans, key=lambda s: s.start)


def _inside(spans, parent, name=None):
    return [s for s in spans if parent.start <= s.start
            and s.end <= parent.end and s is not parent
            and (name is None or s.name == name)]


def _overlap(a, b) -> bool:
    return a.start < b.end and b.start < a.end


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """40 requests through an engine with ``max_batch`` 8, traced; the
    spans and the engine's counters before and after."""
    model, X, y = _fit("nystrom_regularized")
    model.fit(X, y)
    engine = AsyncServeEngine(
        model, policy=BatchPolicy(max_batch=8, max_wait_ms=2.0)).start()
    for k in (1, 2, 3, 5, 8):           # compile every bucket first
        for f in [engine.submit(X[i]) for i in range(k)]:
            f.result(timeout=60)
    before = engine.stats()

    def work():
        futures = []
        for burst in (1, 3, 8, 11, 2, 7, 5, 3):
            futures += [engine.submit(X[i]) for i in range(burst)]
            futures[-1].result(timeout=60)
        for f in futures:
            f.result(timeout=60)
        engine.stop()                   # the worker's last spans close

    spans = _traced(tmp_path_factory.mktemp("serve-trace"), work)
    return spans, before, engine.stats()


def test_one_serve_batch_span_per_batch(served):
    spans, before, after = served
    assert after.served - before.served == 40
    batches = [s for s in spans if s.name == "serve.batch"]
    assert len(batches) == after.batches - before.batches
    assert [b.meta["batch"] for b in batches] == sorted(
        b.meta["batch"] for b in batches)


def test_batch_children_in_order_and_inside(served):
    spans, _, _ = served
    for batch in (s for s in spans if s.name == "serve.batch"):
        children = [s for s in _inside(spans, batch) if s.name in CHILDREN]
        assert [c.name for c in children] == list(CHILDREN)
        for a, b in zip(children, children[1:]):
            assert a.end <= b.start
        assert sum(c.end - c.start for c in children) <= \
            batch.end - batch.start


def test_wait_and_batch_never_overlap(served):
    spans, _, _ = served
    waits = [s for s in spans if s.name == "serve.wait"]
    batches = [s for s in spans if s.name == "serve.batch"]
    assert len(waits) >= len(batches)
    assert not any(_overlap(w, b) for w in waits for b in batches)


def test_batch_metadata_matches_stats(served):
    spans, before, after = served
    batches = [s for s in spans if s.name == "serve.batch"]
    n0 = len(before.batch_sizes)
    assert [b.meta["rows"] for b in batches] == after.batch_sizes[n0:]
    assert [b.meta["bucket"] for b in batches] == after.buckets[n0:]
    for b in batches:
        assert 0 <= b.meta["wait_mean_us"] <= b.meta["wait_max_us"]


def test_fit_spans_nest_and_count_pcg_steps(tmp_path):
    model, X, y = _fit("falkon_pcg")
    spans = _traced(tmp_path, lambda: model.fit(X, y))
    fits = [s for s in spans if s.name == "estimator.fit"]
    assert len(fits) == 1
    for name in ("sampler.score_pass", "sampler.draw", "solver.fit"):
        assert len(_inside(spans, fits[0], name)) == 1, name
    solve = _inside(spans, fits[0], "solver.fit")[0]
    steps = _inside(spans, solve, "solver.pcg_step")
    iters = model.state().iters
    assert iters >= 1
    assert len(steps) == len([s for s in spans
                              if s.name == "solver.pcg_step"]) == iters
    assert [s.meta["it"] for s in steps] == list(range(iters))
