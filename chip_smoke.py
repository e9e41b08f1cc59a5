#!/usr/bin/env python3
"""Smoke run of the sketched-KRR main path on a TPU, through its public
entry points.

    python chip_smoke.py             # one chip: fit, reference check, serve
    python chip_smoke.py --chips 4   # four chips: sharded fit vs one-chip fit

One process drives every chip. It exits non-zero before any work unless
JAX's first device is a TPU (it never falls back to the CPU), and
non-zero when any phase fails; no phase is caught and passed over. The
last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

One chip (no arguments):

* fit    ``SketchedKRR(SketchConfig(kernel=RBFKernel, sampler="rls_fast",
         solver="nystrom_regularized", p=p_scores=1024, backend="auto"))``
         on a seeded draw shaped like UCI HIGGS (d = 28) in float32. It
         runs twice: the first call includes compilation, the second is
         the steady time. The compiled score-pass and cross-block
         programs must hold a Mosaic kernel (``tpu_custom_call``).
* check  predictions on 4096 held-out rows against a plain float32
         ``jax.numpy`` regularized-Nyström fit at the fit's own landmarks,
         under ``jax.default_matmul_precision("highest")``.
* serve  the model published in an ``AsyncServeEngine`` with one padded
         bucket; 256 requests from a client thread, each answer checked
         against ``model.predict`` on the same row. p50/p99 are printed as
         information only.

Four chips (``--chips 4``): only the multi-chip path, ``backend="sharded"``
with ``mesh_shape=4`` and ``inner_backend="pallas"`` on the same data,
compared with the one-chip fit computed in the same process.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import threading
import time
from pathlib import Path

D = 28                  # UCI HIGGS feature count
# UCI HIGGS has 1.1e7 rows; 2^20 is the size this smoke aims at. There the
# regularized-Nyström factor step alone needs 16.00 GB of the v5e's
# 15.75 GB usable HBM (XLA's count when the step is compiled for the
# chip, even jitted; it holds C, C·w and the solve's input and output,
# each (n, p) f32), so n is halved to 2^19.
N_TRAIN = 2**19
N_TEST = 4096
P = 1024                # p = p_scores
LAM = 1e-3
BANDWIDTH = math.sqrt(D)
SEED = 0
N_REQUESTS = 256
BUCKET = 64
REF_BLOCK = 2**16       # reference rows per block

# The fit runs its f32 matmuls at the TPU's default precision, which
# rounds f32 operands to bf16 (unit roundoff 2^-9 ~ 2e-3) -- in the Pallas
# tiles and in the XLA triangular solves and Woodbury products alike --
# and those errors pass through the landmark system, whose condition is
# bounded by 1/LAM. The reference is the same estimator evaluated at full
# f32 precision. A relative RMS deviation of 2e-2 leaves that rounding
# room, and is two orders of magnitude below what a wrong kernel, a
# mis-tiled block or a wrong landmark set produces (O(1)).
REF_RTOL = 2e-2
# Served answers and model.predict are the same f32 program on the same
# rows; only the batch shape (bucket vs. all 256 rows) differs, which may
# reorder a reduction.
SERVE_RTOL = 1e-5
# --chips 4: the sharded score pass sums its p x p Gram across devices in
# a different order; relative RMS of the scores, and of the predictions
# (which may also differ by the few landmarks whose draw flips under that
# rounding).
SHARD_SCORE_RTOL = 1e-2
SHARD_PRED_RTOL = 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def rel_rms(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def higgs_like(n: int, seed: int):
    """Seeded float32 rows of HIGGS's width with a smooth noisy target,
    generated on the device in bulk."""
    import jax
    import jax.numpy as jnp
    kx, kw, ke = jax.random.split(jax.random.key(seed), 3)
    X = jax.random.normal(kx, (n, D), jnp.float32)
    w = jax.random.normal(kw, (D,), jnp.float32) / math.sqrt(D)
    y = (jnp.tanh(X @ w) + 0.5 * jnp.sin(X[:, 0])
         + 0.1 * jax.random.normal(ke, (n,), jnp.float32))
    return X, y


def config(**kw):
    from repro.api import SketchConfig
    from repro.core import RBFKernel
    return SketchConfig(kernel=RBFKernel(BANDWIDTH), p=P, p_scores=P,
                        lam=LAM, seed=SEED, sampler="rls_fast",
                        solver="nystrom_regularized", **kw)


def timed_fit(cfg, X, y, label: str):
    """Fit twice; report the first (compile + run) and the steady call."""
    import jax
    from repro.api import SketchedKRR
    times = []
    model = None
    for _ in range(2):
        model = None                      # free the previous fit first
        t0 = time.perf_counter()
        model = SketchedKRR(cfg).fit(X, y)
        jax.block_until_ready((model.scores(), model.state().beta))
        times.append(time.perf_counter() - t0)
    log(f"{label}: first call {times[0]:.3f} s, steady {times[1]:.3f} s, "
        f"compile ~{times[0] - times[1]:.3f} s")
    return model


def assert_tpu_kernel(name: str, compiled_text: str) -> None:
    if "tpu_custom_call" not in compiled_text:
        fail(f"the compiled {name} program holds no Mosaic kernel "
             f"(tpu_custom_call)")
    log(f"{name}: compiled program holds tpu_custom_call")


def check_kernels_ran(model, X, Xt) -> None:
    """The fit's own score-pass program and a cross block, lowered and
    compiled for this device, must contain the Pallas kernels."""
    import jax
    from repro.core.leverage import _dense_score_pass
    ops = model.ops()
    if ops.name != "pallas":
        fail(f"backend='auto' resolved to {ops.name!r}, not 'pallas'")
    cfg = model.config
    score = _dense_score_pass(ops).lower(X, model.sample().idx,
                                         cfg.lam * cfg.eps, cfg.jitter)
    assert_tpu_kernel("score-pass", score.compile().as_text())
    Z = model.state().landmarks
    cross = jax.jit(ops.cross).lower(Xt, Z)
    assert_tpu_kernel("cross-block", cross.compile().as_text())


def reference_predict(model, X, y, Xt):
    """Plain f32 jax.numpy regularized Nyström at the fit's landmarks.

    With Z = X[idx], sketch weights w, Cs = k(X, Z)·diag(w) and
    A = diag(w) k(Z, Z) diag(w) + nγI = L Lᵀ: F = Cs L⁻ᵀ, and
    f(x) = (k(x, Z)·w) L⁻ᵀ (FᵀF + nλI)⁻¹ Fᵀy. FᵀF and Fᵀy are summed
    over row blocks so no (n, p) array is held.
    """
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import cho_solve, solve_triangular
    sample = model.sample()
    idx, w = sample.idx, sample.weights.astype(jnp.float32)
    Z = X[idx]
    n, h2 = X.shape[0], 2.0 * BANDWIDTH**2

    def k(A, B):
        d2 = (jnp.sum(A * A, 1)[:, None] + jnp.sum(B * B, 1)[None, :]
              - 2.0 * A @ B.T)
        return jnp.exp(-jnp.maximum(d2, 0.0) / h2)

    with jax.default_matmul_precision("highest"):
        A = (k(Z, Z) * w[None, :]) * w[:, None]
        A = 0.5 * (A + A.T) + n * LAM * jnp.eye(P, dtype=jnp.float32)
        L = jnp.linalg.cholesky(A)

        @jax.jit
        def stats(Xb, yb):
            Fb = solve_triangular(L, (k(Xb, Z) * w[None, :]).T,
                                  lower=True).T
            return Fb.T @ Fb, Fb.T @ yb

        G = jnp.zeros((P, P), jnp.float32)
        b = jnp.zeros((P,), jnp.float32)
        for s in range(0, n, REF_BLOCK):
            Gb, bb = stats(X[s:s + REF_BLOCK], y[s:s + REF_BLOCK])
            G, b = G + Gb, b + bb
        H = 0.5 * (G + G.T) + n * LAM * jnp.eye(P, dtype=jnp.float32)
        u = cho_solve((jnp.linalg.cholesky(H), True), b)
        beta = solve_triangular(L.T, u, lower=False)
        return (k(Xt, Z) * w[None, :]) @ beta


def serve_phase(model, Xt) -> None:
    """256 requests from a client thread through one padded bucket."""
    import numpy as np
    from repro.serve import AsyncServeEngine, BatchPolicy
    rows = np.asarray(Xt[:N_REQUESTS])
    want = np.asarray(model.predict(Xt[:N_REQUESTS]))
    policy = BatchPolicy(max_batch=BUCKET, max_wait_ms=2.0,
                         buckets=(BUCKET,))
    futures = []
    with AsyncServeEngine(model, policy=policy) as engine:
        t0 = time.perf_counter()
        engine.predict(rows[0], timeout=600.0)       # compiles the bucket
        log(f"serve: bucket {BUCKET} compile + first answer "
            f"{time.perf_counter() - t0:.3f} s")

        def client():
            for r in rows:
                futures.append(engine.submit(r))

        t0 = time.perf_counter()
        th = threading.Thread(target=client)
        th.start()
        th.join()
        got = np.array([f.result(timeout=600.0).y_hat for f in futures])
        wall = time.perf_counter() - t0
        stats = engine.stats()
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        fail(f"served answers have shape {got.shape} or are not finite")
    bad = ~np.isclose(got, want, rtol=SERVE_RTOL,
                      atol=SERVE_RTOL * float(np.max(np.abs(want))))
    if bad.any():
        fail(f"{int(bad.sum())} of {N_REQUESTS} served answers differ "
             f"from model.predict (max |diff| "
             f"{float(np.max(np.abs(got - want))):.3e})")
    lat = np.asarray(stats.latencies_ms[1:])     # without the warm-up
    log(f"serve: {N_REQUESTS} answers match model.predict "
        f"(rtol {SERVE_RTOL}); {wall:.3f} s for all, "
        f"{stats.batches - 1} batches; latency p50 "
        f"{np.percentile(lat, 50):.3f} ms, p99 {np.percentile(lat, 99):.3f} "
        f"ms (information only)")


def one_chip(X, y, Xt, yt, dev) -> None:
    import jax
    import numpy as np
    model = timed_fit(config(backend="auto"), X, y, "fit")
    check_kernels_ran(model, X, Xt)
    log(f"peak_bytes_in_use after fit: "
        f"{dev.memory_stats()['peak_bytes_in_use']}")

    pred = np.asarray(model.predict(Xt))
    if pred.shape != (N_TEST,) or not np.all(np.isfinite(pred)):
        fail(f"predictions have shape {pred.shape} or are not finite")
    t0 = time.perf_counter()
    ref = np.asarray(jax.block_until_ready(reference_predict(model, X, y,
                                                             Xt)))
    dev_ref = rel_rms(pred, ref)
    log(f"check: reference fit {time.perf_counter() - t0:.3f} s; "
        f"relative RMS deviation from the f32 reference {dev_ref:.3e} "
        f"(tolerance {REF_RTOL}); relative RMS error vs held-out y: fit "
        f"{rel_rms(pred, yt):.4f}, reference {rel_rms(ref, yt):.4f}")
    if not dev_ref <= REF_RTOL:
        fail(f"predictions deviate from the reference by {dev_ref:.3e} "
             f"> {REF_RTOL}")

    serve_phase(model, Xt)


def four_chips(X, y, Xt) -> None:
    import jax
    import numpy as np
    devs = jax.devices()
    if len(devs) < 4:
        fail(f"--chips 4 needs 4 devices, JAX sees {len(devs)}")
    one = timed_fit(config(backend="auto"), X, y, "one-chip fit")
    four = timed_fit(config(backend="sharded", mesh_shape=4,
                            inner_backend="pallas"), X, y, "sharded fit")
    scores = four.scores()
    placed = {s.device for s in scores.addressable_shards}
    if placed != set(devs[:4]):
        fail(f"sharded scores sit on {sorted(d.id for d in placed)}, "
             f"not on all 4 devices")
    rows = {s.data.shape[0] for s in scores.addressable_shards}
    if rows != {N_TRAIN // 4}:
        fail(f"sharded score rows per device are {rows}, not "
             f"{N_TRAIN // 4}")
    log(f"sharded: score rows sit on devices "
        f"{sorted(d.id for d in placed)}, {N_TRAIN // 4} per device")
    same = int(np.sum(np.asarray(one.sample().idx)
                      == np.asarray(four.sample().idx)))
    s_dev = rel_rms(four.scores(), one.scores())
    p_dev = rel_rms(four.predict(Xt), one.predict(Xt))
    log(f"sharded vs one-chip: scores relative RMS {s_dev:.3e} "
        f"(tolerance {SHARD_SCORE_RTOL}), predictions relative RMS "
        f"{p_dev:.3e} (tolerance {SHARD_PRED_RTOL}), {same} of {P} "
        f"landmark draws identical")
    if not s_dev <= SHARD_SCORE_RTOL:
        fail(f"sharded scores deviate by {s_dev:.3e}")
    if not p_dev <= SHARD_PRED_RTOL:
        fail(f"sharded predictions deviate by {p_dev:.3e}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded path and its one-chip "
                         "comparison")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is {dev.platform!r}; "
              f"this script never falls back to the CPU", file=sys.stderr)
        return 1

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import enable_compile_cache
    log(f"jax {jax.__version__}; device_kind {dev.device_kind!r}; "
        f"{len(jax.devices())} device(s); x64 "
        f"{jax.config.jax_enable_x64}; compile cache "
        f"{enable_compile_cache()}")
    log(f"data: HIGGS-shaped float32, n = {N_TRAIN} (2^19: half of the "
        f"2^20 aimed at, see N_TRAIN), d = {D}, {N_TEST} held-out rows; "
        f"p = p_scores = {P}, lambda = {LAM}, bandwidth {BANDWIDTH:.4f}")

    X_all, y_all = higgs_like(N_TRAIN + N_TEST, SEED)
    X, y = X_all[:N_TRAIN], y_all[:N_TRAIN]
    Xt, yt = X_all[N_TRAIN:], y_all[N_TRAIN:]
    del X_all, y_all

    if args.chips == 4:
        four_chips(X, y, Xt)
    else:
        one_chip(X, y, Xt, yt, dev)
    log(f"peak_bytes_in_use: {dev.memory_stats()['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
