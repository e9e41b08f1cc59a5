"""``ModelSlot`` — atomic publish/swap of the O(p) serving state.

The paper's landmark dual is tiny — β ∈ R^p plus the p landmark rows —
so refreshing a served model is a single small-array exchange, not a
redeploy. A ``ModelSlot`` makes that exchange safe under concurrency:

* ``publish(model)`` snapshots the model's serving state into an
  immutable ``PublishedModel`` and swaps it in with one reference
  assignment. Readers never lock.
* ``current()`` returns the live snapshot. A batch that acquired a
  snapshot keeps serving from it even if a swap lands mid-batch — no
  batch ever sees a *torn* dual (half old β, half new landmarks),
  because the dual travels as one immutable tuple.

Compile-free hot swap: for the landmark-family solvers the slot jits
``solver.predict`` **with the state as an argument** (not closed over),
so publishing a refreshed dual of the same shape reuses the compiled
executable — the swap costs one host assignment, zero retraces. Solvers
without an exportable dual (``exact``, ``dnc``) fall back to the
model's own ``make_batched_predict`` (state closed over as constants;
each publish of those recompiles on first use — documented, and not the
production serving path).

Imports of ``repro.api`` are deferred into the methods so
``repro.runtime`` (which builds its sync engine on this slot) stays
importable without the api package loaded — the same contract the old
``KRRServeEngine`` kept.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable

import numpy as np


@dataclasses.dataclass(frozen=True)
class PublishedModel:
    """One immutable published serving snapshot.

    Attributes:
      key:         the slot key this snapshot serves under.
      version:     monotonically increasing per slot (1 = first publish).
      state:       the O(p) landmark-dual pytree passed to the jitted
                   predict, or ``None`` when the snapshot serves through
                   a closed-over fallback predict.
      n_shards:    device count of the model's sharded executor (1 for
                   single-device backends) — batch buckets must be
                   rounded to a multiple of this.
      serve_dtype: the precision policy's quantized serve dtype
                   (``None`` = full fit precision).
      data_dtype:  the config's data dtype; host batches are cast to it
                   before entering the jitted path (mirrors
                   ``SketchedKRR._cast``).
    """

    key: str
    version: int
    state: Any
    n_shards: int
    serve_dtype: str | None
    data_dtype: str | None
    predict_fn: Callable = dataclasses.field(repr=False, compare=False)

    def predict_padded(self, X: Any, bucket: int) -> np.ndarray:
        """Serve a ``(k, dim)`` host batch, or a sequence of ``k`` rows,
        padded to ``bucket`` rows.

        Pads by repeating the last row (the same convention as
        ``SketchedKRR.predict_batched``) so the jitted predict sees one
        shape per bucket, runs it, and trims back to ``k`` results.
        Padding rows are ordinary rows — per-row outputs are independent
        in the landmark form, so padding can't perturb live results.

        The pad happens host-side in numpy: only the fixed ``(bucket,
        dim)`` shape ever reaches jax, so continuous batching with a
        varying live count ``k`` never compiles anything beyond the one
        per-bucket predict (eager jnp padding would JIT a fresh
        concatenate per distinct ``k`` — ~60 ms a pop on CPU, which
        dwarfs the predict itself).

        Inside a ``jax.profiler`` session the host assembly, the upload
        and the predict with its readback record as the spans
        ``serve.pad``, ``serve.upload`` and ``serve.predict``.
        """
        import jax.numpy as jnp
        from jax.profiler import TraceAnnotation

        k = len(X)
        if k > bucket:
            raise ValueError(f"batch of {k} exceeds bucket {bucket}")
        with TraceAnnotation("serve.pad"):
            Xp = np.asarray(X)
            pad = bucket - k
            if pad:
                Xp = np.concatenate(
                    [Xp, np.broadcast_to(Xp[-1:], (pad,) + Xp.shape[1:])])
        with TraceAnnotation("serve.upload"):
            if self.data_dtype is None:
                Xb = jnp.asarray(Xp)
            else:
                Xb = jnp.asarray(Xp, dtype=jnp.dtype(self.data_dtype))
        with TraceAnnotation("serve.predict"):
            if self.state is not None:
                y = self.predict_fn(self.state, Xb)
            else:
                y = self.predict_fn(Xb)
            return np.asarray(y)[:k]


class ModelSlot:
    """Holds the live ``PublishedModel`` behind an atomic publish/swap.

    ``publish`` may be called from any thread (a background
    ``partial_fit → finalize`` refresher, typically) while serve workers
    read ``current()`` concurrently; the swap is a single reference
    assignment, and every snapshot is immutable, so readers are always
    consistent without taking a lock.
    """

    def __init__(self, model: Any = None, *, key: str = "default"):
        self.key = key
        self._lock = threading.Lock()
        self._entry: PublishedModel | None = None
        # One jitted state-as-argument predict per config, reused across
        # publishes — this is what makes a hot swap compile-free.
        self._fn: Callable | None = None
        self._fn_cfg: Any = None
        if model is not None:
            self.publish(model)

    @property
    def version(self) -> int:
        """Version of the live snapshot (0 before the first publish)."""
        entry = self._entry
        return 0 if entry is None else entry.version

    def current(self) -> PublishedModel:
        """The live snapshot; raises if nothing was published yet.

        Callers serve a whole batch from ONE ``current()`` acquisition —
        that single read is the atomicity contract.
        """
        entry = self._entry
        if entry is None:
            raise RuntimeError(
                f"model slot {self.key!r} has no published model yet — "
                "call publish(model) first")
        return entry

    def _dual_predict_fn(self, cfg: Any) -> Callable:
        """The jitted ``(state, Xb) -> y`` serve path for ``cfg``.

        Built once per config and cached on the slot: the fitted dual is
        a *runtime argument*, so republishing a same-shape dual hits the
        existing XLA executable. Replicates the quantized-serving rule of
        ``SketchedKRR.make_batched_predict`` (batch cast to
        ``serve_dtype``, contraction in the serving accumulation dtype).
        """
        if self._fn is None or self._fn_cfg != cfg:
            import jax

            from ..api.solvers import SOLVERS

            solver = SOLVERS.get(cfg.solver)
            serve = cfg.precision.serve()
            if serve is None:
                def serve_predict(st, Xb):
                    return solver.predict(cfg, st, Xb)
            else:
                qcfg = cfg.replace(precision=cfg.precision.for_serving())

                def serve_predict(st, Xb):
                    return solver.predict(qcfg, st, Xb.astype(serve))
            self._fn = jax.jit(serve_predict)
            self._fn_cfg = cfg
        return self._fn

    def publish(self, model: Any) -> int:
        """Snapshot ``model``'s serving state and swap it live.

        ``model`` is a fitted ``repro.api.SketchedKRR``. For the
        landmark-family solvers the snapshot is the exported O(p)
        ``ServingState`` (decoupled from the estimator — later
        ``partial_fit``/``finalize`` rounds on the same object can't
        mutate what's being served); other solvers are served through
        their own jitted fixed-batch predict. Returns the new version.
        Raises ``repro.api.NotFittedError`` for unfitted models.
        """
        from ..api.estimator import solver_state_from_serving

        cfg = model.config
        ops = model.ops() if callable(getattr(model, "ops", None)) else None
        n_shards = int(getattr(ops, "n_shards", 1) or 1)
        try:
            serving = model.export_serving_state()
        except TypeError:
            serving = None      # no landmark dual (exact / dnc / custom)
        if serving is not None:
            state = solver_state_from_serving(serving)
            fn = self._dual_predict_fn(cfg)
        else:
            state = None
            fn = model.make_batched_predict()   # fails fast if unfitted
        with self._lock:
            entry = PublishedModel(
                key=self.key, version=self.version + 1, state=state,
                n_shards=n_shards,
                serve_dtype=getattr(cfg.precision, "serve_dtype", None),
                data_dtype=cfg.data_dtype, predict_fn=fn)
            self._entry = entry     # the atomic swap
        return entry.version
