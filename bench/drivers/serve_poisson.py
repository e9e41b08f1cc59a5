"""``serve_poisson``: an open loop of single-row requests into
``AsyncServeEngine``, sent on a Poisson schedule at the mix's fixed rate;
each request is timed from the moment it was due to the moment its answer
was set."""
from __future__ import annotations

import time
from functools import partial

import jax
import numpy as np

from harness.data import seed31
from harness.drivers import Answers, CellDriver, settle


class PoissonServe(CellDriver):
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

    def schedule(self, seconds: float, pool_rows: int):
        """``(due, rows)``: each request's send time after the window's
        start, in s, and the held-out row it asks for."""
        rate = float(self.traffic["rate_per_s"])
        n = int(round(rate * seconds))
        q = (np.arange(n) + 0.5) / n
        rng = np.random.default_rng(seed31(self.seed))
        due = np.cumsum(rng.permutation(-np.log1p(-q) / rate))
        rows = rng.permutation(np.arange(n) % pool_rows)
        return due, rows

    def window(self, seconds: float) -> dict:
        due, rows = self.schedule(seconds, len(self.pool))
        n = len(due)
        answers = Answers(n)
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
        _, f_ref = self._reference_parts(idx, self.cfg.seed)
        want = f_ref[self.rows[self.served]]
        got = self.answers[self.served]
        return {"served_rel_rms": self.ref.rel_rms(got, want),
                "served_max_gap": self.ref.max_gap(got, want)}


Driver = PoissonServe
