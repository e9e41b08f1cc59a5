"""The ``SketchedKRR`` estimator — one object for the whole paper pipeline.

    config = SketchConfig(kernel=RBFKernel(1.5), p=200, lam=1e-3,
                          sampler="rls_fast", solver="nystrom")
    model = SketchedKRR(config).fit(X, y)
    y_hat = model.predict(X_test)            # out-of-sample Nyström extension
    l_hat = model.scores()                   # sampler's leverage estimates
    report = model.risk(f_star, noise_std)   # closed-form eq.-(4) risk

``fit`` draws one PRNG key from ``config.seed`` and splits it into
independent sampler/solver streams, so a fit is a pure function of
(config, X, y). ``predict_batched`` runs a jit-compiled fixed-batch predict
(padding the tail batch), which is the path ``runtime.serve_loop.KRRServeEngine``
drives under continuous batching.

Every kernel block the registered sampler/Nyström pipeline evaluates — the
sampler score pass, the solver's column sketch, and the serve-time test
blocks — streams through the ``KernelOps`` backend selected by
``config.backend`` (xla | pallas | streaming | sharded | auto; see
``repro.core.backends``; only the ``dnc`` solver's inner partition loop
remains backend-managed by its core module). The jitted serving path
therefore hits the Pallas MXU tiles on TPU; the streaming backend keeps
every per-chunk compute intermediate at O(block_rows · p) — its score pass
and predict matvec never materialize an (n, p) / (batch, p) block (the
fitted factor itself remains O(n·p) model state); and the sharded backend
(``config.mesh_shape`` devices, per-shard ``config.inner_backend``
executor) row-shards fit AND predict over the mesh with only p-sized
collectives, so ``fit``/``predict``/``predict_batched`` and the
``KRRServeEngine`` all execute SPMD with no code changes.

``config.precision`` selects the dtype of every stage (see
``repro.core.precision``): inputs are cast to ``data_dtype`` at
fit/predict time (superseding the legacy ``dtype`` field), the backends
accumulate and factor per the policy, and ``make_batched_predict`` /
``predict_batched`` serve quantized when ``serve_dtype`` is set (bf16
blocks + f32 accumulation) with full precision as the unset fallback.

Fits scale past device memory two ways (``repro.api.out_of_core``):
``fit(source)`` streams a ``repro.data.chunks`` source (in-memory /
generator / memory-mapped ``.npy``) through the chunked driver — X, C and
B are never materialized, cross-chunk state is O(p²) — and
``partial_fit(chunk)`` + ``finalize()`` accumulate the same sufficient
statistics incrementally, freezing the landmark set after the first
chunk's score pass. Out-of-core models predict/serve exactly like
in-memory ones; only the closed-form diagnostics (``risk``,
``predict_train``) need the in-memory factor and say so when asked.
"""
from __future__ import annotations

import os
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array
from jax.profiler import TraceAnnotation, annotate_function

from ..core.backends import KernelOps, ops_for_config
from ..core.krr import RiskReport, empirical_risk
from ..core.nystrom import ColumnSample
from ..data.chunks import ChunkSource, as_chunk_source
from ..data.sparse import CsrMatrix, SparseChunkSource, is_sparse_matrix
from .config import SketchConfig
from .out_of_core import SPARSE_CHUNK_SOLVERS, fit_from_source
from .samplers import SAMPLERS, Sampler
from .solvers import NystromState, SOLVERS, Solver


class NotFittedError(RuntimeError):
    """Raised when a method that needs a fitted model runs before
    ``fit``/``finalize`` (or when an out-of-core fit is asked for a
    diagnostic that was never computed)."""


class ServingState(NamedTuple):
    """The swap-able O(p) serving state of a landmark-family fit.

    Everything the Nyström extension f̂(x) = k(x, Z)·β needs at serve
    time — the dual β, the landmark rows Z, and the Theorem-3 sketch
    column weights — plus the solver key the state belongs to. This is
    the paper's point made operational: the *model* is p numbers and p
    rows, so shipping a refreshed fit to a serving process (or hot-
    swapping it into ``repro.serve.ModelSlot``) is a small-array
    exchange, never a redeploy.

    Produced by ``SketchedKRR.export_serving_state``; consumed by
    ``SketchedKRR.import_serving_state`` and by
    ``solver_state_from_serving`` (which rebuilds the solver-level state
    the jitted predict path takes as an argument).
    """

    beta: Array
    landmarks: Array
    col_weights: Array | None
    solver: str


def solver_state_from_serving(serving: ServingState) -> NystromState:
    """Rebuild a predict-capable solver state from a ``ServingState``.

    The returned ``NystromState`` carries only the serving triple (its
    factor/coefficient slots are ``None``), which is exactly what the
    landmark solvers' ``predict`` consumes — and being a NamedTuple of
    arrays, it is a pytree the serve plane can pass straight into a
    jitted ``(state, X) -> y`` function as a runtime argument.
    Training-set diagnostics (``risk``, ``predict_train``) are not
    reconstructible from O(p) state and stay unavailable.
    """
    return NystromState(approx=None, alpha=None, beta=serving.beta,
                        landmarks=serving.landmarks,
                        col_weights=serving.col_weights)


class SketchedKRR:
    """Sketched kernel ridge regression with pluggable sampler and solver.

    The sampler and solver are resolved from the string-keyed registries at
    construction time, so a typo fails before any compute happens.
    """

    def __init__(self, config: SketchConfig):
        self.config = config
        self._sampler: Sampler = SAMPLERS.get(config.sampler)
        self._solver: Solver = SOLVERS.get(config.solver)
        self._state: Any = None
        self._sample: ColumnSample | None = None
        self._scores: Array | None = None
        self._X_train: Array | None = None
        self._predict_jit: Callable[[Array], Array] | None = None
        self._accum: Any = None       # live ChunkAccumulator (partial_fit)
        self._n_seen: int = 0

    # ------------------------------------------------------------- fitting

    def _cast(self, arr: Array) -> Array:
        """Array in the config's data dtype (``precision.data_dtype``
        supersedes the legacy ``dtype`` field; None keeps the input)."""
        dt = self.config.data_dtype
        if isinstance(arr, CsrMatrix):
            return arr.cast(None if dt is None else jnp.dtype(dt))
        if dt is None:
            return jnp.asarray(arr)
        return jnp.asarray(arr, dtype=jnp.dtype(dt))

    @partial(annotate_function, name="estimator.fit")
    def fit(self, X, y: Array | None = None) -> "SketchedKRR":
        """Fit from an in-memory array — or out-of-core from a chunk source.

        Three input shapes:
          * ``fit(X, y)`` with arrays — the classic in-memory fit (unless
            ``config.chunk_rows`` is set, which streams the same rows
            through the chunked driver in ``chunk_rows`` blocks).
          * ``fit(source)`` with a ``repro.data.chunks.ChunkSource``
            (targets ride inside the source) — the out-of-core fit: the
            Theorem-4 pass and the solver's sufficient statistics stream
            chunk-by-chunk, X/C/B are never materialized, and cross-chunk
            state is O(p²).
          * ``fit(path, y_path)`` with ``.npy`` paths — shorthand for a
            ``MemmapChunkSource`` at ``config.chunk_rows`` (default 4096).
          * ``fit(factory)`` with a zero-arg callable yielding
            ``(X_block, y_block)`` pairs — shorthand for a
            ``GeneratorChunkSource`` (the factory is re-invoked once per
            pass).

        A fit is a pure function of (config, rows): one key is drawn from
        ``config.seed`` and split into sampler/solver streams on every
        path, and chunked fits are bit-identical across source kinds at
        equal ``chunk_rows``.
        """
        cfg = self.config
        if isinstance(X, ChunkSource):
            if y is not None:
                raise ValueError("fit(source): targets ride inside the "
                                 "chunk source, drop the y argument")
            return self._fit_source(X)
        if isinstance(X, (str, os.PathLike)) or callable(X):
            # .npy path(s) or a zero-arg block factory (yielding (X, y)
            # pairs) — both coerce to a chunk source
            return self._fit_source(as_chunk_source(
                X, y, cfg.chunk_rows or 4096))
        if is_sparse_matrix(X):
            # CSR rows (CsrMatrix or scipy.sparse) route through the
            # chunked driver — the sparse executors consume CSR chunks
            # natively, so the fit never densifies X. One whole-matrix
            # chunk when chunk_rows is unset; either way this is the same
            # path as fit(SparseChunkSource), so in-memory and chunked
            # sparse fits are bit-identical at equal chunk_rows.
            if y is None:
                raise TypeError("fit(X, y) needs targets; only chunk "
                                "sources carry their own y")
            if not isinstance(X, CsrMatrix):
                X = CsrMatrix.from_scipy(X)
            return self._fit_source(SparseChunkSource(
                X, np.asarray(y), cfg.chunk_rows or max(X.shape[0], 1)))
        if y is None:
            raise TypeError("fit(X, y) needs targets; only chunk sources "
                            "carry their own y")
        if cfg.chunk_rows is not None:
            return self._fit_source(as_chunk_source(
                self._cast(X), self._cast(y), cfg.chunk_rows))
        X = self._cast(X)
        y = self._cast(y)
        key_sample, key_solve = jax.random.split(jax.random.key(cfg.seed))
        self._key_sample = key_sample
        self._sample = None
        self._scores = None
        self._X_train = X
        self._accum = None
        # Solvers that ignore the sample (exact, dnc) skip the sampling
        # pass at fit time; scores()/sample() run it lazily from the same
        # key, so diagnostics stay available and deterministic.
        sample = self._run_sampler() if self._solver.needs_sample else None
        with TraceAnnotation("solver.fit"):
            self._state = self._solver.fit(cfg, X, y, sample, key_solve)
        self._predict_jit = None
        return self

    def _fit_source(self, source: ChunkSource) -> "SketchedKRR":
        """Out-of-core fit through ``repro.api.out_of_core``."""
        self._sample = self._scores = self._X_train = None
        self._accum = None
        res = fit_from_source(self.config, self._solver, source)
        self._sample, self._scores = res.sample, res.scores
        self._n_seen = res.n_rows
        self._state = res.state
        self._predict_jit = None
        return self

    def partial_fit(self, X: Array, y: Array) -> "SketchedKRR":
        """Fold one row chunk into the fit's sufficient statistics.

        The incremental twin of ``fit(source)`` for data that arrives
        over time rather than sitting in a file. The first chunk runs the
        configured sampler *on that chunk* and freezes the landmark set
        and sketch weights (the FALKON-style incremental protocol — valid
        when chunks are exchangeable draws from the same distribution);
        every chunk, including the first, then folds into the solver's
        accumulator — O(p²) state for the Nyström solvers, row buffering
        for ``exact``. Call ``finalize()`` to solve; more
        ``partial_fit`` + ``finalize`` rounds keep refining the same
        model from the enlarged statistics.

        Chunks may vary in size, but each new size retraces the jitted
        accumulation step — feed fixed-size chunks when throughput
        matters.
        """
        cfg = self.config
        X = self._cast(X)
        y = self._cast(y)
        if isinstance(X, CsrMatrix) and cfg.solver not in \
                SPARSE_CHUNK_SOLVERS:
            raise ValueError(
                f"solver {cfg.solver!r} buffers raw rows host-side and "
                f"cannot consume CSR chunks without densifying them; "
                f"sparse partial_fit supports: "
                f"{', '.join(SPARSE_CHUNK_SOLVERS)}")
        if self._accum is None:
            key_sample, key_solve = jax.random.split(
                jax.random.key(cfg.seed))
            self._key_sample, self._key_solve = key_sample, key_solve
            begin = getattr(self._solver, "begin_chunked", None)
            if begin is None:
                raise ValueError(
                    f"solver {cfg.solver!r} does not support incremental "
                    "fitting; use one of: exact, nystrom, "
                    "nystrom_regularized, falkon_pcg")
            self._state = None
            self._sample = self._scores = self._X_train = None
            self._n_seen = 0
            landmarks = None
            if self._solver.needs_sample:
                out = self._sampler(key_sample, cfg.kernel, X, cfg)
                self._sample, self._scores = out.sample, out.scores
                landmarks = X[out.sample.idx]
            self._accum = begin(cfg, landmarks, self._sample)
        self._accum.add(X, y)
        self._n_seen += X.shape[0]
        self._predict_jit = None
        return self

    def finalize(self) -> "SketchedKRR":
        """Solve from the statistics accumulated by ``partial_fit``.

        O(p³) for the Nyström solvers — cheap enough to call after every
        chunk if mid-stream predictions are wanted; the accumulator stays
        live, so ``partial_fit`` can keep feeding rows afterwards.
        """
        if self._accum is None:
            raise NotFittedError("call partial_fit(X, y) before finalize()")
        self._state = self._accum.finalize(self._n_seen, self._key_solve)
        self._predict_jit = None
        return self

    def _run_sampler(self) -> ColumnSample:
        if self._X_train is None:
            raise NotFittedError(
                "sampler diagnostics were not computed during this "
                "out-of-core fit (the solver consumed no sample) and "
                "cannot be recomputed without the in-memory training set")
        out = self._sampler(self._key_sample, self.config.kernel,
                            self._X_train, self.config)
        self._sample, self._scores = out.sample, out.scores
        return self._sample

    def _require_fit(self) -> None:
        if self._state is None:
            if self._accum is not None:
                raise NotFittedError(
                    "partial_fit has accumulated chunks but the model is "
                    "not solved yet — call finalize() first")
            raise NotFittedError("call fit(X, y) before this method")

    # ---------------------------------------------------------- prediction

    def predict(self, X_test: Array) -> Array:
        """Out-of-sample predictions f̂(x) = k(x, Z)·β at arbitrary points
        (the Nyström extension for the sketched solvers), through the
        configured kernel backend."""
        self._require_fit()
        return self._solver.predict(self.config, self._state,
                                    self._cast(X_test))

    def predict_train(self) -> Array:
        """Predictions at the training points, through the solver's cached
        factors (zero fresh kernel evaluations for the registered solvers;
        user solvers without a ``predict_train`` fall back to ``predict``)."""
        self._require_fit()
        fn = getattr(self._solver, "predict_train", None)
        if fn is None:
            return self._solver.predict(self.config, self._state,
                                        self._X_train)
        return fn(self.config, self._state, self._X_train)

    def make_batched_predict(self) -> Callable[[Array], Array]:
        """Jit-compiled predict over a fixed batch shape (the serve path).

        The fitted state is closed over as compile-time constants; the
        returned callable retraces only when the batch shape changes, so a
        serving loop that pads to a fixed batch size compiles exactly once.

        When ``config.precision.serve_dtype`` is set, this path is the
        quantized server: the batch is cast to ``serve_dtype``, the kernel
        blocks are evaluated there (e.g. bf16 Pallas tiles on TPU), and
        the landmark contraction accumulates in ``accum_dtype`` (f32 when
        unset). Leaving ``serve_dtype`` unset serves at full fit precision
        — the config-selected fallback; plain ``predict`` always does.
        """
        self._require_fit()
        if self._predict_jit is None:
            cfg, solver, state = self.config, self._solver, self._state
            serve = cfg.precision.serve()
            if serve is None:
                fn = lambda Xb: solver.predict(cfg, state, Xb)
            else:
                qcfg = cfg.replace(precision=cfg.precision.for_serving())
                fn = lambda Xb: solver.predict(qcfg, state,
                                               Xb.astype(serve))
            self._predict_jit = jax.jit(fn)
        return self._predict_jit

    def predict_batched(self, X_test: Array, batch_size: int = 256) -> Array:
        """Predict in fixed-size jitted batches, padding the tail batch."""
        self._require_fit()
        if isinstance(X_test, CsrMatrix):
            raise TypeError(
                "predict_batched slices/pads dense test batches, which "
                "CsrMatrix does not support; call predict(X_test) — the "
                "sparse cross block is internally nnz-tiled already")
        X_test = self._cast(X_test)
        n = X_test.shape[0]
        if n == 0:
            return self.predict(X_test)  # empty in, empty out — no padding
        fn = self.make_batched_predict()
        outs = []
        for start in range(0, n, batch_size):
            blk = X_test[start:start + batch_size]
            pad = batch_size - blk.shape[0]
            if pad:
                blk = jnp.concatenate(
                    [blk, jnp.broadcast_to(blk[-1:], (pad,) + blk.shape[1:])])
            outs.append(fn(blk)[:batch_size - pad if pad else batch_size])
        return jnp.concatenate(outs)[:n]

    # ------------------------------------------------------- serving state

    def export_serving_state(self) -> ServingState:
        """The O(p) state a serving process needs — and nothing else.

        Snapshots (β, Z, column weights) out of the fitted solver state
        into an immutable ``ServingState``. The snapshot is decoupled
        from this estimator: later ``partial_fit``/``finalize`` rounds
        refine the model without touching previously exported states,
        which is what makes atomic hot swap through
        ``repro.serve.ModelSlot`` safe. Only the landmark-family solvers
        (``nystrom``, ``nystrom_regularized``, ``distributed``) carry
        this form; ``exact``/``dnc`` raise ``TypeError`` — their fitted
        state is O(n) and must be served through
        ``make_batched_predict``.
        """
        self._require_fit()
        beta = getattr(self._state, "beta", None)
        landmarks = getattr(self._state, "landmarks", None)
        if beta is None or landmarks is None:
            raise TypeError(
                f"solver {self.config.solver!r} has no O(p) landmark "
                "dual to export — its fitted state scales with the "
                "training set; serve it through make_batched_predict() "
                "instead")
        return ServingState(
            beta=beta, landmarks=landmarks,
            col_weights=getattr(self._state, "col_weights", None),
            solver=self.config.solver)

    def import_serving_state(self, serving: ServingState) -> "SketchedKRR":
        """Install an exported O(p) serving state into this estimator.

        The receiving config's solver must match the exporting one
        (``ValueError`` otherwise — the dual's semantics are
        solver-specific). After import the model predicts bit-equal to
        the exporter through every predict path; training-set
        diagnostics (``risk``, ``scores``, ``predict_train``) are not
        part of the O(p) state and raise their usual descriptive errors.
        """
        if serving.solver != self.config.solver:
            raise ValueError(
                f"serving state was exported from solver "
                f"{serving.solver!r} but this estimator is configured "
                f"for {self.config.solver!r}; duals are not portable "
                "across solvers")
        self._state = solver_state_from_serving(serving)
        self._sample = self._scores = self._X_train = None
        self._accum = None
        self._predict_jit = None
        return self

    # ---------------------------------------------------------- diagnostics

    def scores(self) -> Array:
        """The sampler's unnormalized score vector (leverage estimates for
        the rls_* samplers, K_ii for diagonal, ones for uniform). Computed
        lazily if the solver didn't consume a sample during fit. For an
        out-of-core fit the stored chunked-pass scores are returned (for
        ``partial_fit`` models they cover the landmark-selection chunk);
        lazy recomputation needs the in-memory training set."""
        self._require_fit()
        if self._scores is None:
            self._run_sampler()
        return self._scores

    def sample(self) -> ColumnSample:
        """The Theorem-3 column draw behind the fit (indices,
        distribution, sketch weights); computed lazily like ``scores``."""
        self._require_fit()
        if self._sample is None:
            self._run_sampler()
        return self._sample

    def state(self) -> Any:
        """The raw fitted solver state (solver-specific named tuple)."""
        self._require_fit()
        return self._state

    def ops(self) -> KernelOps:
        """The resolved ``KernelOps`` executor this model's kernel blocks
        route through (``config.backend`` after ``auto`` resolution)."""
        return ops_for_config(self.config)

    def risk(self, f_star: Array, noise_std: float) -> RiskReport:
        """Closed-form eq.-(4) risk when the solver has one; otherwise the
        empirical risk (1/n)‖f̂ − f*‖² at the training points."""
        self._require_fit()
        f_star = self._cast(f_star)
        report = self._solver.risk(self.config, self._state, f_star,
                                   noise_std)
        if report is None:
            r = empirical_risk(self.predict_train(), f_star)
            report = RiskReport(r, jnp.asarray(np.nan), jnp.asarray(np.nan))
        return report

    def __repr__(self) -> str:
        fitted = "fitted" if self._state is not None else "unfitted"
        return (f"SketchedKRR(sampler={self.config.sampler!r}, "
                f"solver={self.config.solver!r}, p={self.config.p}, "
                f"lam={self.config.lam}, {fitted})")
