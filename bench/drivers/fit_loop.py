"""``fit_loop``: a closed loop of whole fits, back to back, on rows that
stay resident on the device. ``fit_s`` is the window's wall time, from
its start to the end of its last fit, over the fits it completed: every
fit is ``SketchedKRR(cfg).fit(X, y)`` and its held-out predictions, the
gaps between fits included."""
from __future__ import annotations

import time
import traceback
from typing import NamedTuple

import jax
import numpy as np

from harness.data import fit_seed, seed31
from harness.drivers import CellDriver, settle


class Fit(NamedTuple):
    """What the comparison needs of one fit. The arrays stay on the device
    until the window has closed, so no copy to the host lies between
    two fits."""

    seed: int              # its SketchConfig.seed
    idx: jax.Array         # its Theorem-3 draw
    scores: jax.Array      # its Theorem-4 scores of every training row
    pred: jax.Array        # its predictions at the held-out rows


class FitLoop(CellDriver):
    """Fit ``i`` draws its sketch from ``fit_seed(seed, i)``: every seed
    fits the same rows shape with the same solver, so seeds differ in the
    rows and the sketches, not in the work. Set-up makes the rows and runs
    fit 0 untimed, so every program a fit runs is compiled, or loaded from
    the persistent cache, before the window."""

    def setup(self):
        self._data()
        self._fit(0)
        settle()

    def _fit(self, i: int):
        """Fit ``i``: its time in s, from the call to its duals and
        held-out predictions ready, and its ``Fit``. The model is dropped
        when this returns, before the next fit starts."""
        from repro.api import SketchedKRR
        cfg = self.cfg.replace(seed=fit_seed(self.seed, i))
        t0 = time.perf_counter()
        model = SketchedKRR(cfg).fit(self.X, self.y)
        fit = Fit(cfg.seed, model.sample().idx, model.scores(),
                  model.predict(self.Xt))
        jax.block_until_ready((model.state().beta, fit))
        return time.perf_counter() - t0, fit

    def window(self, seconds: float) -> dict:
        """Fits until the window closes; the one running at the close is
        awaited and counted, and there is always at least one."""
        start = time.perf_counter()
        close = start + seconds
        self.times, self.fits = [], []
        failed = attempted = 0
        while attempted == 0 or time.perf_counter() < close:
            attempted += 1
            try:
                t, fit = self._fit(attempted)
            except Exception:                 # noqa: BLE001 — counted
                traceback.print_exc()
                failed += 1
                continue
            self.times.append(t)
            self.fits.append(fit)
        wall = time.perf_counter() - start
        return {"fit_s": wall / len(self.times) if self.times
                else float("nan"),
                "attempted": attempted, "failed": failed}

    def counters(self) -> dict:
        return {"fit_times_s": self.times}

    def check(self) -> dict:
        """One fit of the window, drawn from the seed, against the
        reference."""
        if not self.fits:                     # every fit failed
            return {}
        rng = np.random.default_rng(seed31(self.seed))
        fit = self.fits[int(rng.integers(len(self.fits)))]
        self.fits = None
        seed, (idx, scores, pred) = fit.seed, map(np.asarray, fit[1:])
        del fit
        ref_scores, ref_pred = self._reference_parts(idx, seed)
        return {"scores_rel_rms": self.ref.rel_rms(scores, ref_scores),
                "pred_rel_rms": self.ref.rel_rms(pred, ref_pred),
                "pred_max_gap": self.ref.max_gap(pred, ref_pred)}


Driver = FitLoop
