"""KernelOps backend layer: parity matrix (kernel × backend × dtype at
non-tile-aligned shapes), streaming-memory behaviour, auto resolution, and
the no-direct-gram architectural invariant."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import MaxIntermediate, NoDirectGram, assert_audit, lint_file
from repro.api import BACKENDS, SAMPLERS, SketchConfig, SketchedKRR
from repro.core import (BernoulliKernel, LinearKernel, PolynomialKernel,
                        RBFKernel, fast_ridge_leverage, ops_for,
                        resolve_backend)
from repro.core.backends import StreamingOps, XlaOps
from repro.kernels import ops as kops
from repro.kernels import ref as kref

# deliberately NOT multiples of the Pallas tile sizes (256/128) or of the
# streaming block_rows used below — exercises every padded-tail path
N, P_COLS, DIM = 301, 37, 5
BLOCK_ROWS = 64
DTYPES = [jnp.float32, jnp.float64]
BACKEND_NAMES = sorted(BACKENDS.available())

KERNEL_INSTANCES = {
    "linear": LinearKernel(),
    "rbf": RBFKernel(1.3),
    # scale ≈ dim keeps poly kernel values O(1) — the f32 parity tolerance
    # is meaningful only for normalized kernels
    "poly": PolynomialKernel(degree=2, scale=float(DIM), offset=0.7),
    "bernoulli": BernoulliKernel(b=1),
}


def _tol(dtype):
    return dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 else \
        dict(rtol=1e-10, atol=1e-10)


def _X(kernel_name, n=N, dtype=jnp.float64, seed=0):
    key = jax.random.key(seed)
    if kernel_name == "bernoulli":  # 1-D kernel on [0, 1]
        return jax.random.uniform(key, (n, 1), dtype)
    return jax.random.normal(key, (n, DIM), dtype)


def _pair(kernel_name, backend, dtype, seed=0):
    kernel = KERNEL_INSTANCES[kernel_name]
    X = _X(kernel_name, dtype=dtype, seed=seed)
    return (X, ops_for(kernel, backend, block_rows=BLOCK_ROWS),
            ops_for(kernel, "xla"))


class TestBlockParity:
    """Every backend must reproduce the xla reference block-for-block,
    including the padded tails at non-tile-aligned n and p."""

    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("backend", BACKEND_NAMES)
    @pytest.mark.parametrize("kernel_name", sorted(KERNEL_INSTANCES))
    def test_columns_and_cross(self, kernel_name, backend, dtype):
        X, ops, xla = _pair(kernel_name, backend, dtype)
        idx = jax.random.randint(jax.random.key(1), (P_COLS,), 0, N)
        np.testing.assert_allclose(np.asarray(ops.columns(X, idx)),
                                   np.asarray(xla.columns(X, idx)),
                                   **_tol(dtype))
        Z = _X(kernel_name, n=P_COLS, dtype=dtype, seed=2)
        np.testing.assert_allclose(np.asarray(ops.cross(X, Z)),
                                   np.asarray(xla.cross(X, Z)), **_tol(dtype))

    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("backend", BACKEND_NAMES)
    def test_matvec_rmatvec(self, backend, dtype):
        X, ops, xla = _pair("rbf", backend, dtype)
        Z = _X("rbf", n=P_COLS, dtype=dtype, seed=2)
        v = jax.random.normal(jax.random.key(3), (P_COLS,), dtype)
        u = jax.random.normal(jax.random.key(4), (N,), dtype)
        np.testing.assert_allclose(np.asarray(ops.matvec(X, Z, v)),
                                   np.asarray(xla.matvec(X, Z, v)),
                                   **_tol(dtype))
        np.testing.assert_allclose(np.asarray(ops.rmatvec(X, Z, u)),
                                   np.asarray(xla.rmatvec(X, Z, u)),
                                   **_tol(dtype))

    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("backend", BACKEND_NAMES)
    def test_gram_matvec(self, backend, dtype):
        """The fused v ↦ k(X,Z)ᵀ(k(X,Z)v) seam behind falkon_pcg — must
        match rmatvec∘matvec on every backend, including the masked
        padded-tail rows (k(0, z) ≠ 0, so an unmasked pad would leak),
        for both (p,) and multi-output (p, k) operands."""
        X, ops, xla = _pair("rbf", backend, dtype)
        Z = _X("rbf", n=P_COLS, dtype=dtype, seed=2)
        v = jax.random.normal(jax.random.key(3), (P_COLS,), dtype)
        ref = xla.rmatvec(X, Z, xla.matvec(X, Z, v))
        np.testing.assert_allclose(np.asarray(ops.gram_matvec(X, Z, v)),
                                   np.asarray(ref), **_tol(dtype))
        V = jax.random.normal(jax.random.key(8), (P_COLS, 3), dtype)
        ref2 = xla.rmatvec(X, Z, xla.matvec(X, Z, V))
        np.testing.assert_allclose(np.asarray(ops.gram_matvec(X, Z, V)),
                                   np.asarray(ref2), **_tol(dtype))

    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("backend", BACKEND_NAMES)
    def test_leverage_scores(self, backend, dtype):
        B = jax.random.normal(jax.random.key(5), (N, P_COLS), dtype)
        ops = ops_for(KERNEL_INSTANCES["rbf"], backend,
                      block_rows=BLOCK_ROWS)
        xla = ops_for(KERNEL_INSTANCES["rbf"], "xla")
        np.testing.assert_allclose(
            np.asarray(ops.leverage_scores(B, 1e-2, N)),
            np.asarray(xla.leverage_scores(B, 1e-2, N)), **_tol(dtype))

    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("kind,fn,ref_fn", [
        ("rbf", lambda X, Z: kops.rbf_block(X, Z, bandwidth=1.3),
         lambda X, Z: kref.rbf_block_ref(X, Z, 1.3)),
        ("linear", kops.linear_block, kref.linear_block_ref),
        ("poly",
         lambda X, Z: kops.poly_block(X, Z, degree=3, scale=2.0, offset=0.5),
         lambda X, Z: kref.poly_block_ref(X, Z, 3, 2.0, 0.5)),
    ])
    def test_kernel_block_padded_tail(self, kind, fn, ref_fn, dtype):
        """Zero-padded Z rows (p=37 → lane-padded to 128) produce k(x, 0) ≠ 0
        inside the tile — the sliced output must still match the reference
        exactly, in both precisions (satellite: padded-tail correctness)."""
        X = jax.random.normal(jax.random.key(6), (N, DIM), dtype)
        Z = jax.random.normal(jax.random.key(7), (P_COLS, DIM), dtype)
        out = fn(X, Z)
        assert out.shape == (N, P_COLS) and out.dtype == dtype
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_fn(X, Z)),
                                   **_tol(dtype))


class TestPipelineParity:
    """Sampler scores and SketchedKRR predictions agree across backends."""

    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("backend", BACKEND_NAMES)
    @pytest.mark.parametrize("kernel_name", sorted(KERNEL_INSTANCES))
    def test_rls_fast_scores(self, kernel_name, backend, dtype):
        kernel = KERNEL_INSTANCES[kernel_name]
        X = _X(kernel_name, dtype=dtype)
        cfg = dict(kernel=kernel, p=24, lam=1e-2, p_scores=48, seed=11)
        sampler = SAMPLERS.get("rls_fast")
        ref = sampler(jax.random.key(8), kernel, X,
                      SketchConfig(**cfg, backend="xla"))
        got = sampler(jax.random.key(8), kernel, X,
                      SketchConfig(**cfg, backend=backend,
                                   block_rows=BLOCK_ROWS))
        np.testing.assert_allclose(np.asarray(got.scores),
                                   np.asarray(ref.scores), **_tol(dtype))

    @pytest.mark.parametrize("dtype_name", ["float32", "float64"])
    @pytest.mark.parametrize("backend", BACKEND_NAMES)
    @pytest.mark.parametrize("kernel_name", sorted(KERNEL_INSTANCES))
    def test_predict(self, kernel_name, backend, dtype_name):
        """Same seed + the (backend-independent) diagonal distribution ⇒
        identical sampled columns ⇒ predictions must agree to backend
        tolerance for every kernel."""
        dtype = jnp.dtype(dtype_name)
        kernel = KERNEL_INSTANCES[kernel_name]
        X = _X(kernel_name, dtype=dtype)
        y = jnp.sin(3.0 * X[:, 0])
        X_test = _X(kernel_name, n=53, dtype=dtype, seed=21)
        cfg = dict(kernel=kernel, p=24, lam=1e-2, seed=13,
                   sampler="diagonal", solver="nystrom_regularized",
                   dtype=dtype_name)
        ref = SketchedKRR(SketchConfig(**cfg, backend="xla")).fit(X, y)
        got = SketchedKRR(SketchConfig(**cfg, backend=backend,
                                       block_rows=BLOCK_ROWS)).fit(X, y)
        assert bool(jnp.all(ref.sample().idx == got.sample().idx))
        np.testing.assert_allclose(np.asarray(got.predict(X_test)),
                                   np.asarray(ref.predict(X_test)),
                                   **_tol(dtype))
        np.testing.assert_allclose(
            np.asarray(got.predict_batched(X_test, batch_size=16)),
            np.asarray(ref.predict(X_test)), **_tol(dtype))

    @pytest.mark.parametrize("backend", BACKEND_NAMES)
    @pytest.mark.parametrize("solver", ["nystrom", "nystrom_regularized"])
    def test_multi_output_y(self, solver, backend):
        """β is (p, k) for multi-output y — the weight folding in predict
        and the streaming matvec/rmatvec must broadcast, not flatten."""
        X = _X("rbf")
        Y = jnp.stack([jnp.sin(3.0 * X[:, 0]), X[:, 1] ** 2], axis=-1)
        cfg = dict(kernel=KERNEL_INSTANCES["rbf"], p=24, lam=1e-2, seed=13,
                   sampler="diagonal", solver=solver)
        ref = SketchedKRR(SketchConfig(**cfg, backend="xla")).fit(X, Y)
        got = SketchedKRR(SketchConfig(**cfg, backend=backend,
                                       block_rows=BLOCK_ROWS)).fit(X, Y)
        X_test = _X("rbf", n=53, seed=21)
        pred = got.predict(X_test)
        assert pred.shape == (53, 2)
        np.testing.assert_allclose(np.asarray(pred),
                                   np.asarray(ref.predict(X_test)),
                                   rtol=1e-10, atol=1e-10)

    @pytest.mark.parametrize("backend", BACKEND_NAMES)
    def test_matvec_rmatvec_2d(self, backend):
        X, ops, xla = _pair("rbf", backend, jnp.float64)
        Z = _X("rbf", n=P_COLS, seed=2)
        V = jax.random.normal(jax.random.key(3), (P_COLS, 3))
        U = jax.random.normal(jax.random.key(4), (N, 3))
        np.testing.assert_allclose(np.asarray(ops.matvec(X, Z, V)),
                                   np.asarray(xla.matvec(X, Z, V)),
                                   rtol=1e-10, atol=1e-10)
        np.testing.assert_allclose(np.asarray(ops.rmatvec(X, Z, U)),
                                   np.asarray(xla.rmatvec(X, Z, U)),
                                   rtol=1e-10, atol=1e-10)

    def test_serve_engine_through_backend(self):
        from repro.runtime import KRRRequest, KRRServeEngine
        X = _X("rbf")
        y = jnp.sin(3.0 * X[:, 0])
        preds = {}
        for backend in ("xla", "streaming"):
            cfg = SketchConfig(kernel=KERNEL_INSTANCES["rbf"], p=24,
                               lam=1e-2, seed=13, sampler="diagonal",
                               backend=backend, block_rows=BLOCK_ROWS)
            engine = KRRServeEngine(SketchedKRR(cfg).fit(X, y),
                                    batch_size=16)
            for i in range(40):
                engine.submit(KRRRequest(uid=i, x=np.asarray(X[i])))
            done = engine.run()
            preds[backend] = np.array(
                [r.y_hat for r in sorted(done, key=lambda r: r.uid)])
        np.testing.assert_allclose(preds["streaming"], preds["xla"],
                                   rtol=1e-10, atol=1e-10)


class TestBf16Accum:
    """Precision-policy column of the parity matrix: bf16 inputs with f32
    accumulation (the MXU-native pairing) on the pallas and streaming
    backends. bf16 carries ~3 significant digits, so parity vs the f32
    reference is loose — what IS hard-asserted is finiteness everywhere
    (the padded tails and the p×p solves must never amplify the quantized
    blocks into NaN/Inf)."""

    BF16_BACKENDS = ["pallas", "streaming"]
    TOL = dict(rtol=5e-2, atol=5e-2)

    @staticmethod
    def _bf16_pair(kernel_name, backend):
        kernel = KERNEL_INSTANCES[kernel_name]
        X = _X(kernel_name, dtype=jnp.float32)
        ops = ops_for(kernel, backend, block_rows=BLOCK_ROWS)
        xla = ops_for(kernel, "xla")
        return X, X.astype(jnp.bfloat16), ops, xla

    @pytest.mark.parametrize("backend", BF16_BACKENDS)
    @pytest.mark.parametrize("kernel_name", ["linear", "rbf"])
    def test_columns_and_cross(self, kernel_name, backend):
        X, Xb, ops, xla = self._bf16_pair(kernel_name, backend)
        idx = jax.random.randint(jax.random.key(1), (P_COLS,), 0, N)
        got = ops.columns(Xb, idx)
        assert got.dtype == jnp.bfloat16  # blocks stay in the data dtype
        g = np.asarray(got, np.float64)
        assert np.all(np.isfinite(g))
        np.testing.assert_allclose(
            g, np.asarray(xla.columns(X, idx), np.float64), **self.TOL)

    @pytest.mark.parametrize("backend", BF16_BACKENDS)
    def test_matvec_accumulates_f32(self, backend):
        X, Xb, ops, xla = self._bf16_pair("rbf", backend)
        Z = _X("rbf", n=P_COLS, dtype=jnp.float32, seed=2)
        v = jax.random.normal(jax.random.key(3), (P_COLS,), jnp.float32)
        got = ops.matvec(Xb, Z.astype(jnp.bfloat16), v)
        # bf16 blocks contracted against an f32 dual accumulate in f32
        assert got.dtype == jnp.float32
        g = np.asarray(got, np.float64)
        assert np.all(np.isfinite(g))
        np.testing.assert_allclose(
            g, np.asarray(xla.matvec(X, Z, v), np.float64), **self.TOL)

    @pytest.mark.parametrize("backend", BF16_BACKENDS)
    def test_score_pass_finite(self, backend):
        """The fused Thm-4 pass end-to-end in bf16 blocks: the p×p core
        (widest-float solves + floored jitter) must keep every score
        finite and in [0, 1]."""
        kernel = KERNEL_INSTANCES["rbf"]
        Xb = _X("rbf", dtype=jnp.float32).astype(jnp.bfloat16)
        cfg = dict(kernel=kernel, p=24, lam=1e-2, p_scores=48, seed=11)
        sampler = SAMPLERS.get("rls_fast")
        out = sampler(jax.random.key(8), kernel, Xb,
                      SketchConfig(**cfg, backend=backend,
                                   block_rows=BLOCK_ROWS))
        s = np.asarray(out.scores, np.float64)
        assert np.all(np.isfinite(s))
        assert s.min() >= 0.0 and s.max() <= 1.05


class TestStreamingMemory:
    def test_fit_at_tiny_block_rows_matches_dense(self):
        """The acceptance check: a fit streamed at block_rows ≪ n must
        reproduce the dense result — fit and predict both work while no
        per-chunk intermediate ever exceeds O(block_rows · p)."""
        X = _X("rbf", n=400)
        y = jnp.sin(3.0 * X[:, 0]) + 0.2 * X[:, 1]
        cfg = dict(kernel=KERNEL_INSTANCES["rbf"], p=32, lam=1e-2, seed=5,
                   sampler="rls_fast", solver="nystrom_regularized",
                   p_scores=64)
        dense = SketchedKRR(SketchConfig(**cfg, backend="xla")).fit(X, y)
        tiny = SketchedKRR(SketchConfig(**cfg, backend="streaming",
                                        block_rows=16)).fit(X, y)
        X_test = _X("rbf", n=77, seed=6)
        np.testing.assert_allclose(np.asarray(tiny.predict(X_test)),
                                   np.asarray(dense.predict(X_test)),
                                   rtol=1e-9, atol=1e-9)
        np.testing.assert_allclose(np.asarray(tiny.scores()),
                                   np.asarray(dense.scores()),
                                   rtol=1e-9, atol=1e-9)

    def test_score_pass_never_materializes_np(self):
        """Structural check: the jaxpr of the streamed Theorem-4 score pass
        contains no intermediate of size ≥ n·p — C and B never exist."""
        n, p, br = 2048, 64, 32
        ker = KERNEL_INSTANCES["rbf"]
        X = jax.random.normal(jax.random.key(0), (n, 4))
        ops = ops_for(ker, "streaming", block_rows=br)
        assert isinstance(ops, StreamingOps) and ops.streams_score_pass
        idx = jax.random.randint(jax.random.key(1), (p,), 0, n)

        def pass_only(X):
            return ops.score_pass(X, idx, 1e-2, 1e-10)[0]

        jaxpr = jax.make_jaxpr(pass_only)(X)
        # the (n, p) block this backend exists to avoid
        assert_audit(jaxpr, [MaxIntermediate(n * p)],
                     where="streaming-score-pass")

    def test_streamed_result_reports_no_factor(self):
        ker = KERNEL_INSTANCES["rbf"]
        X = _X("rbf")
        res = fast_ridge_leverage(ker, X, 1e-2, 40, jax.random.key(2),
                                  ops=ops_for(ker, "streaming", BLOCK_ROWS))
        assert res.B is None and res.row_sq is not None
        dense = fast_ridge_leverage(ker, X, 1e-2, 40, jax.random.key(2))
        assert dense.B is not None
        np.testing.assert_allclose(
            np.asarray(res.row_sq),
            np.asarray(jnp.sum(dense.B * dense.B, axis=-1)),
            rtol=1e-10, atol=1e-10)


class TestResolution:
    def test_registry_entries(self):
        assert set(BACKENDS.available()) == {"xla", "pallas", "streaming",
                                            "sharded"}

    def test_auto_resolution_follows_platform(self, monkeypatch):
        assert resolve_backend("auto") == (
            "pallas" if jax.default_backend() == "tpu" else "xla")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert resolve_backend("auto") == "pallas"
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert resolve_backend("auto") == "xla"

    def test_auto_resolution_follows_dtype_on_tpu(self, monkeypatch):
        """Mosaic has no f64: on a TPU ``auto`` sends f64 blocks to XLA."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert resolve_backend("auto", jnp.float32) == "pallas"
        assert resolve_backend("auto", jnp.bfloat16) == "pallas"
        assert resolve_backend("auto", jnp.float64) == "xla"
        assert resolve_backend("pallas", jnp.float64) == "pallas"
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert resolve_backend("auto", jnp.float32) == "xla"

    def test_f64_blocks_on_tpu(self, monkeypatch):
        """An ``auto``-built pallas executor computes f64 blocks with the
        dense XLA formula on a TPU; an explicit ``pallas`` one raises,
        naming the dtype and the policy field."""
        X = jax.random.normal(jax.random.key(0), (40, 5), jnp.float64)
        ker = KERNEL_INSTANCES["rbf"]
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        auto = ops_for(ker, "auto")
        assert auto.name == "pallas" and auto.auto
        np.testing.assert_array_equal(
            np.asarray(auto.cross(X, X[:7])),
            np.asarray(ops_for(ker, "xla").cross(X, X[:7])))
        with pytest.raises(ValueError,
                           match=r"float64.*Precision\.data_dtype"):
            ops_for(ker, "pallas").cross(X, X[:7])

    def test_unknown_backend_fails_loudly(self):
        with pytest.raises(KeyError, match="streaming"):
            resolve_backend("bogus")
        with pytest.raises(ValueError, match="backend"):
            SketchConfig(kernel=KERNEL_INSTANCES["rbf"], p=4,
                         backend="bogus")
        with pytest.raises(ValueError, match="block_rows"):
            SketchConfig(kernel=KERNEL_INSTANCES["rbf"], p=4, block_rows=0)

    def test_needs_interpret_rechecks_platform(self, monkeypatch):
        """Satellite: detection must key on the *current* backend, not on
        whichever platform was active at the first (formerly cached) call."""
        first = kops._needs_interpret()
        monkeypatch.setattr(kops.jax, "default_backend", lambda: "tpu")
        assert kops._needs_interpret() is False
        monkeypatch.setattr(kops.jax, "default_backend", lambda: "cpu")
        assert kops._needs_interpret() is True
        monkeypatch.undo()
        assert kops._needs_interpret() == first

    def test_estimator_exposes_resolved_ops(self):
        cfg = SketchConfig(kernel=KERNEL_INSTANCES["rbf"], p=8,
                           backend="streaming", block_rows=17)
        X = _X("rbf", n=40)
        model = SketchedKRR(cfg).fit(X, jnp.sin(X[:, 0]))
        ops = model.ops()
        assert isinstance(ops, StreamingOps) and ops.block_rows == 17
        assert isinstance(
            SketchedKRR(cfg.replace(backend="auto")).ops(),
            XlaOps if jax.default_backend() != "tpu" else object)


class TestSatellites:
    def test_bernoulli_coeffs_lru_cached(self):
        from repro.core.kernels import _bernoulli_poly_coeffs
        _bernoulli_poly_coeffs.cache_clear()
        first = _bernoulli_poly_coeffs(4)
        assert _bernoulli_poly_coeffs.cache_info().misses == 1
        assert _bernoulli_poly_coeffs(4) is first  # cached, not recomputed
        assert _bernoulli_poly_coeffs.cache_info().hits == 1
        # gram/diag on the kernel hit the cache rather than re-running the
        # O(m²) recursion
        ker = BernoulliKernel(b=2)
        x = jnp.linspace(0.0, 1.0, 16)
        ker.gram(x, x)
        hits_after_gram = _bernoulli_poly_coeffs.cache_info().hits
        ker.diag(x)
        assert _bernoulli_poly_coeffs.cache_info().hits > hits_after_gram

    def test_no_direct_gram_call_sites(self):
        """Acceptance: the dense ``kernel.gram`` seam lives only in the
        backend implementations — everything else routes through
        KernelOps. Pinned by the ``no-direct-gram`` lint (AST-based, so
        comments/strings don't false-positive), file by file so a failure
        names the offender."""
        src = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
        rule = NoDirectGram()
        for path in sorted(src.rglob("*.py")):
            rel = path.relative_to(src.parent).as_posix()
            if rule.skips(rel):
                continue
            findings = lint_file(path, rel, [rule])
            assert not findings, "\n".join(str(f) for f in findings)
