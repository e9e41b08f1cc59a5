"""The general drivers that a traffic mix names by its ``driver`` key.

``serve_poisson``   an open loop of single-row requests into
                    ``AsyncServeEngine``, sent on a Poisson schedule at the
                    mix's fixed rate; each request is timed from the moment
                    it was due to the moment its answer was set.

This is the only module of the benchmark that imports the program. Each
driver has ``setup()``, ``window(seconds)``, ``counters()`` and
``check()``: ``check`` runs after the window, frees the program's state
and returns each compared number, computed against ``reference``.
"""
from __future__ import annotations

import gc
import threading
import time
from functools import partial

import jax
import numpy as np

from . import reference as ref
from .data import make_data, seed31


def sketch_config(config: dict, seed: int, precision: dict | None = None):
    """The ``SketchConfig`` a configuration file describes."""
    from repro.api import SketchConfig
    from repro.core import RBFKernel
    from repro.core.precision import Precision
    kw = dict(config.get("options", {}))
    if precision:
        kw["precision"] = Precision(**precision)
    return SketchConfig(
        kernel=RBFKernel(config["bandwidth"]), p=config["p"],
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


class _Driver:
    def __init__(self, cell, seed: int, devices, precision=None):
        self.cell, self.seed, self.devices = cell, seed, devices
        self.config, self.traffic = cell.config, cell.traffic
        self.cfg = sketch_config(self.config, seed, precision)

    def _data(self):
        self.X, self.y, self.Xt, _ = make_data(self.config, self.seed)

    def _reference_parts(self, idx):
        """Reference scores and held-out predictions for a fit whose
        Theorem-3 draw was ``idx``."""
        c = self.config
        X, y, Xt = self.X, self.y, self.Xt
        self.X = self.y = None
        idx_s = ref.score_landmarks(self.cfg.seed, c["n_train"],
                                    c["p_scores"])
        l_ref = ref.scores(X, idx_s, c["lam"] * c["eps"], c["bandwidth"])
        f_ref = ref.predict(X, y, Xt, idx, l_ref, c["lam"], c["bandwidth"])
        return l_ref, f_ref


class _Answers:
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


class PoissonServe(_Driver):
    """Single-row requests on a Poisson schedule at a fixed rate.

    Every seed sends the same number of requests with the same set of
    gaps (the exponential distribution's quantiles at the mix's rate) in
    an order drawn from the seed, so seeds differ in arrival order and in
    the rows asked for, not in the load offered.
    """

    def setup(self):
        from repro.api import SketchedKRR
        from repro.serve import AsyncServeEngine, BatchPolicy
        self._data()
        model = SketchedKRR(self.cfg).fit(self.X, self.y)
        jax.block_until_ready(model.state().beta)
        self.model = model
        self.pool = np.asarray(self.Xt)
        self.engine = AsyncServeEngine(
            model, policy=BatchPolicy(**self.traffic["policy"])).start()
        self._warm_buckets()
        self.batches_before = len(self.engine.stats().batch_sizes)
        settle()

    def _warm_buckets(self):
        """Compile every padded bucket the policy can pick, by bursts of
        each bucket's size, until the engine has served each of them."""
        policy = self.engine.policy
        sizes = sorted({policy.bucket_for(k) for k in
                        range(1, policy.max_batch + 1)})
        for _ in range(20):
            seen = set(self.engine.stats().buckets)
            todo = [b for b in sizes if b not in seen]
            if not todo:
                return
            for b in todo:
                futures = [self.engine.submit(self.pool[i % len(self.pool)])
                           for i in range(b)]
                for f in futures:
                    f.result(timeout=600.0)
        raise RuntimeError(f"buckets {todo} never served during warm-up")

    def window(self, seconds: float) -> dict:
        rate = float(self.traffic["rate_per_s"])
        n = int(round(rate * seconds))
        q = (np.arange(n) + 0.5) / n
        rng = np.random.default_rng(seed31(self.seed))
        due = np.cumsum(rng.permutation(-np.log1p(-q) / rate))
        rows = rng.permutation(np.arange(n) % len(self.pool))
        answers = _Answers(n)
        late = np.empty(n)
        submit, pool, record = self.engine.submit, self.pool, answers.record
        t0 = time.perf_counter()
        for i in range(n):
            target = t0 + due[i]
            wait = target - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            submit(pool[rows[i]]).add_done_callback(partial(record, i))
            late[i] = time.perf_counter() - target
        close = time.perf_counter()
        # an answer that comes late is late, not missing: wait for each,
        # a minute past the close at most
        answers.all_settled.wait(timeout=60.0)
        ok = ~np.isnan(answers.done)
        lat_ms = (answers.done[ok] - (t0 + due[ok])) * 1e3
        self.rows, self.answers, self.served = rows, answers.values, ok
        self.lat_ms = lat_ms
        self.late_ms = late * 1e3
        self.window_s = close - t0
        p50, p95, p99 = np.percentile(lat_ms, [50, 95, 99])
        return {"serve_p50_ms": float(p50), "serve_p95_ms": float(p95),
                "serve_p99_ms": float(p99), "attempted": n,
                "failed": int(n - ok.sum())}

    def counters(self) -> dict:
        sizes = self.engine.stats().batch_sizes[self.batches_before:]
        return {"batch_sizes": sizes,
                "latency_p99_ms": float(np.percentile(self.lat_ms, 99)),
                "generator_late_ms": {
                    "p50": float(np.percentile(self.late_ms, 50)),
                    "p99": float(np.percentile(self.late_ms, 99)),
                    "max": float(np.max(self.late_ms))}}

    def check(self) -> dict:
        self.engine.stop()
        idx = self.model.sample().idx
        self.model = self.engine = None
        _, f_ref = self._reference_parts(idx)
        want = f_ref[self.rows[self.served]]
        got = self.answers[self.served]
        return {"served_rel_rms": ref.rel_rms(got, want),
                "served_max_gap": ref.max_gap(got, want)}


DRIVERS = {"serve_poisson": PoissonServe}
