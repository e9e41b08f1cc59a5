"""Runtime pieces: optimizer math, serve engine (LM and quantized KRR),
ssm decode/train parity, hlo cost analyzer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import AdamWConfig, adamw_update, init_adamw, schedule
from repro.runtime import Request, ServeEngine
from tests.test_models_smoke import small_cfg


class TestAdamW:
    def test_quadratic_convergence(self):
        cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1,
                          total_steps=200, grad_clip=0.0, min_lr_frac=1.0)
        params = {"w": jnp.asarray([5.0, -3.0])}
        state = init_adamw(params)
        for _ in range(150):
            grads = {"w": 2 * params["w"]}
            params, state, _ = adamw_update(cfg, grads, state, params)
        assert float(jnp.max(jnp.abs(params["w"]))) < 0.05

    def test_schedule_warmup_and_cosine(self):
        cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=110,
                          min_lr_frac=0.1)
        assert float(schedule(cfg, jnp.asarray(5))) == pytest.approx(0.5)
        assert float(schedule(cfg, jnp.asarray(10))) == pytest.approx(
            1.0, rel=1e-3)
        assert float(schedule(cfg, jnp.asarray(110))) == pytest.approx(
            0.1, rel=1e-3)

    def test_grad_clip(self):
        cfg = AdamWConfig(grad_clip=1.0, warmup_steps=1, total_steps=5)
        params = {"w": jnp.zeros(4)}
        state = init_adamw(params)
        _, _, metrics = adamw_update(cfg, {"w": jnp.full((4,), 100.0)},
                                     state, params)
        assert float(metrics["grad_norm"]) == pytest.approx(200.0)


class TestServeEngine:
    def test_continuous_batching_serves_all(self):
        cfg = small_cfg("musicgen-medium")  # audio path exercises embeds? no
        cfg = small_cfg("gemma2-2b")
        from repro.models import init_model
        params = init_model(cfg, jax.random.key(0))
        engine = ServeEngine(cfg, params, slots=2, max_len=256)
        rng = np.random.default_rng(0)
        for uid in range(5):
            engine.submit(Request(
                uid=uid,
                prompt=rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
                max_new_tokens=4))
        done = engine.run()
        assert len(done) == 5
        assert all(len(r.generated) == 4 for r in done)

    def test_slot_isolation(self):
        """A request admitted into a freed slot must generate the same
        tokens as when served alone (start-offset masking works)."""
        cfg = small_cfg("chatglm3-6b")
        from repro.models import init_model
        params = init_model(cfg, jax.random.key(0))
        prompt = np.asarray([5, 9, 17, 3, 11], np.int32)

        solo = ServeEngine(cfg, params, slots=1, max_len=128)
        solo.submit(Request(uid=0, prompt=prompt, max_new_tokens=5))
        ref = solo.run()[0].generated

        eng = ServeEngine(cfg, params, slots=1, max_len=256)
        rng = np.random.default_rng(3)
        eng.submit(Request(uid=1, prompt=rng.integers(
            0, cfg.vocab_size, 9).astype(np.int32), max_new_tokens=6))
        eng.submit(Request(uid=2, prompt=prompt, max_new_tokens=5))
        out = {r.uid: r.generated for r in eng.run()}
        assert out[2] == ref


class TestKRRServeQuantized:
    """The quantized KRR serve path (precision.serve_dtype): bf16 kernel
    blocks + f32 accumulation must produce finite predictions within 1e-2
    rtol of full-precision f32 serving, on the parity-matrix shapes
    (n=301, p=24, batch not dividing n)."""

    @staticmethod
    def _serve(serve_dtype, backend="auto"):
        from repro.api import Precision, SketchConfig, SketchedKRR
        from repro.core import RBFKernel
        from repro.runtime import KRRRequest, KRRServeEngine
        X = jax.random.normal(jax.random.key(0), (301, 5)).astype(
            jnp.float32)
        y = jnp.sin(3.0 * X[:, 0])
        cfg = SketchConfig(kernel=RBFKernel(1.3), p=24, lam=1e-2, seed=13,
                           sampler="diagonal", solver="nystrom_regularized",
                           dtype="float32", backend=backend,
                           precision=Precision(serve_dtype=serve_dtype))
        engine = KRRServeEngine(SketchedKRR(cfg).fit(X, y), batch_size=16)
        for i in range(40):
            engine.submit(KRRRequest(uid=i, x=np.asarray(X[i])))
        done = engine.run()
        assert len(done) == 40
        return engine, np.array(
            [r.y_hat for r in sorted(done, key=lambda r: r.uid)])

    @pytest.mark.parametrize("backend", ["auto", "pallas", "streaming"])
    def test_bf16_serve_matches_f32(self, backend):
        eng32, f32 = self._serve(None, backend)
        engbf, bf16 = self._serve("bfloat16", backend)
        assert eng32.serve_dtype is None          # config-selected fallback
        assert engbf.serve_dtype == "bfloat16"
        assert np.all(np.isfinite(bf16))
        np.testing.assert_allclose(bf16, f32, rtol=1e-2, atol=5e-3)

    def test_serve_at_data_dtype_equals_unset_fallback(self):
        """``serve_dtype`` equal to the data dtype must be byte-identical
        to leaving it unset: every cast the quantized path inserts
        (batch→serve, blocks→data, contraction→accum) resolves to a no-op
        at that point, so the two compiled serve functions are the same
        computation."""
        _, fallback = self._serve(None)
        _, pinned = self._serve("float32")
        np.testing.assert_array_equal(pinned, fallback)


class TestSSMDecodeParity:
    def test_chunked_vs_recurrent(self):
        """SSD chunked training forward == step-by-step recurrence."""
        cfg = small_cfg("mamba2-780m")
        from repro.models.ssm import (init_ssm, init_ssm_state, ssm_block,
                                      ssm_decode_step)
        p = init_ssm(jax.random.key(0), cfg)
        B, S = 2, 32
        u = jax.random.normal(jax.random.key(1), (B, S, cfg.d_model),
                              jnp.float32) * 0.5
        full = ssm_block(p, cfg, u)
        st = init_ssm_state(cfg, B, dtype=jnp.float32)
        outs = []
        for i in range(S):
            o, st = ssm_decode_step(p, cfg, u[:, i:i + 1], st)
            outs.append(o[:, 0])
        step = jnp.stack(outs, 1)
        np.testing.assert_allclose(np.asarray(step), np.asarray(full),
                                   atol=2e-3)


class TestHloCostAnalyzer:
    def test_scan_trip_multiplication(self):
        from repro.launch.hlo_cost import analyze_hlo

        def body(h, w):
            return jnp.tanh(h @ w), None

        W = jnp.zeros((8, 128, 128))
        h0 = jnp.zeros((16, 128))

        def f(h, W):
            h, _ = jax.lax.scan(body, h, W)
            return h

        c = jax.jit(f).lower(h0, W).compile()
        r = analyze_hlo(c.as_text())
        assert r.flops == pytest.approx(2 * 16 * 128 * 128 * 8)

    def test_collective_bytes_counted(self):
        from repro.launch.hlo_cost import analyze_hlo
        from jax.sharding import PartitionSpec as P
        mesh = jax.make_mesh((1,), ("d",),
                             axis_types=(jax.sharding.AxisType.Auto,))

        def f(x):
            return jax.lax.psum(x, "d")

        fn = jax.shard_map(f, mesh=mesh, in_specs=P("d"), out_specs=P())
        c = jax.jit(fn).lower(jnp.zeros((64,), jnp.float32)).compile()
        r = analyze_hlo(c.as_text())
        assert r.collectives.get("all-reduce", 0) == 64 * 4
