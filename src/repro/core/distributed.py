"""Distributed (multi-device) ridge-leverage Nyström KRR via shard_map.

The paper's algorithm is embarrassingly row-parallel: every step touches K
only through p sampled columns, and the rows of C = K[:, I] are independent.
Since PR 3 this module is a thin orchestration layer over the ``sharded``
``KernelOps`` backend (``repro.core.backends.ShardedOps``): X is row-sharded
over the ``data`` axis, each device's C/B blocks come from the per-shard
*inner* executor (xla | pallas tiles | streaming row-chunks), and the only
collectives are p-sized — BᵀB (one psum of a p×p block) for the leverage
scores, and Fᵀv / FᵀF psums inside the Woodbury solve. No kernel matrix is
ever evaluated here directly; every block flows through the executor seam.

Also included: FALKON-style preconditioned CG, in two ranks.
``distributed_pcg_krr`` (PR 3) is the exact-K n-space solver — its matvec
necessarily all-gathers (X, v) per iteration, trading the p-sized-collective
guarantee for an exact solve. Since PR 7 the *first-class* production route
is the landmark-space pair :func:`falkon_pcg_krr` /
:func:`falkon_pcg_from_stats` behind ``SOLVERS["falkon_pcg"]``: PCG on the
p-dimensional normal equations of the footnote-4 sketch (the very system
``nystrom_regularized`` factors directly, so the two are parity-testable),
preconditioned by the weighted landmark overlap M = Ws² + nλA. Its iterate
is p-sized, its matvec streams every kernel block through the configured
``KernelOps`` backend (``gram_matvec``), and its chunked twin runs off
one-pass O(p²) statistics — no O(n·p) state, any n.
"""
from __future__ import annotations

import math
from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

# shard_map / data_mesh live in backends now (the executor owns the mesh);
# re-exported here so existing ``from repro.core.distributed import ...``
# call sites keep working.
from .backends import (DEFAULT_BLOCK_ROWS, KernelOps, ShardedOps,  # noqa: F401
                       data_mesh, jittered_cholesky, shard_map_norep,
                       validated_device_count)
from .eigenpro import landmark_solve_dtypes, regularized_penalty
from .hostsync import concrete_float
from .kernels import Kernel
from .precision import Precision, storage_floored_jitter


def _normalize_mesh(mesh: Mesh | int | tuple[int, ...] | None,
                    axis: str) -> Mesh:
    """One Mesh for a mesh-or-count argument — every entry point here
    shares it, and the count case validates through the same
    ``validated_device_count`` as ``ShardedOps.n_shards``, so all mesh
    inputs are accepted (and rejected) identically. A real ``Mesh`` is
    returned verbatim: its device selection and ordering are the
    caller's."""
    if isinstance(mesh, Mesh):
        return mesh
    return data_mesh(validated_device_count(mesh), axis)


def _sharded_ops(kernel: Kernel, mesh: Mesh | int | tuple[int, ...] | None,
                 axis: str, inner_backend: str,
                 block_rows: int | None,
                 precision: Precision | None = None) -> ShardedOps:
    mesh = _normalize_mesh(mesh, axis)
    return ShardedOps(kernel=kernel,
                      block_rows=block_rows or DEFAULT_BLOCK_ROWS,
                      inner_backend=inner_backend,
                      precision=precision or Precision(),
                      axis_name=tuple(mesh.shape)[0],
                      device_mesh=mesh)


# ------------------------------------------------------ distributed leverage

class DistributedRLS(NamedTuple):
    scores: Array   # (n,) row-sharded λ-ridge leverage approximations
    B: Array        # (n, p) row-sharded Nyström factor
    d_eff: Array    # scalar (replicated)


def distributed_fast_leverage(
    kernel: Kernel,
    X: Array,
    landmarks: Array,      # (p, dim) replicated landmark points
    lam: float,
    mesh: Mesh | int | None = None,
    *,
    axis: str = "data",
    jitter: float = 1e-10,
    inner_backend: str = "auto",
    block_rows: int | None = None,
    precision: Precision | None = None,
) -> DistributedRLS:
    """Sharded-executor version of the §3.5 algorithm.

    Delegates to ``ShardedOps.leverage_pass``: per device C_blk = k(X_blk, Z)
    through the ``inner_backend`` executor, B_blk = C_blk L^{-T}, one p×p
    psum of B_blkᵀB_blk, scores from the shared (G + nλI)^{-1} Cholesky —
    all p-dimensional algebra replicated, all n-dimensional data sharded.
    ``mesh`` may be a Mesh, a device count, or None (all devices); n need
    not divide the device count (padded rows are masked). ``precision``
    (optional) is the per-stage dtype policy threaded into the executor.
    """
    ops = _sharded_ops(kernel, mesh, axis, inner_backend, block_rows,
                       precision)
    scores, B, d_eff = ops.leverage_pass(X, landmarks, lam, jitter)
    return DistributedRLS(scores, B, d_eff)


# ------------------------------------------- distributed Woodbury KRR solve

def distributed_nystrom_krr(
    B: Array, y: Array, lam: float, mesh: Mesh | int | None = None, *,
    axis: str = "data",
) -> Array:
    """α = (BBᵀ + nλI)^{-1} y with B row-sharded: two psums of size p / p×p."""
    n = y.shape[0]
    mesh = _normalize_mesh(mesh, axis)
    axis = tuple(mesh.shape)[0]
    d = math.prod(mesh.shape.values())
    pad = -n % d
    if pad:  # zero rows of B / y drop out of both psums and the update
        B = jnp.pad(B, ((0, pad), (0, 0)))
        y = jnp.pad(y, (0, pad))

    def local(B_blk: Array, y_blk: Array) -> Array:
        p = B_blk.shape[1]
        G = jax.lax.psum(B_blk.T @ B_blk, axis) + n * lam * jnp.eye(
            p, dtype=B_blk.dtype)
        By = jax.lax.psum(B_blk.T @ y_blk, axis)
        c, low = jax.scipy.linalg.cho_factor(0.5 * (G + G.T))
        z = jax.scipy.linalg.cho_solve((c, low), By)
        return (y_blk - B_blk @ z) / (n * lam)

    fn = shard_map_norep(local, mesh=mesh,
                         in_specs=(P(axis, None), P(axis)),
                         out_specs=P(axis))
    return fn(B, y)[:n]


# ------------------------------------ FALKON-style preconditioned CG (bonus)

class PCGResult(NamedTuple):
    alpha: Array
    residual_norms: Array  # (iters,)


def distributed_pcg_krr(
    kernel: Kernel,
    X: Array,
    y: Array,
    lam: float,
    B: Array,                 # row-sharded Nyström factor (preconditioner)
    mesh: Mesh | int | None = None,
    *,
    axis: str = "data",
    iters: int = 30,
    inner_backend: str = "auto",
    block_rows: int | None = None,
) -> PCGResult:
    """Solve (K + nλI)α = y by CG, preconditioned with (BBᵀ + nλI)^{-1}.

    The matvec Kv is blockwise through the per-shard inner executor: each
    device holds X_blk and computes k(X_blk, X) @ v with an all-gather of
    (X, v) — O(n²/d) FLOPs/device and one all-gather of n·dim bytes per
    iteration (with ``inner_backend="streaming"`` the per-device block is
    additionally row-chunked). The Nyström preconditioner clusters the
    spectrum so ~tens of iterations suffice (FALKON; beyond-paper
    production solver). Padded tail rows are masked so every CG iterate
    stays exactly zero there.
    """
    ops = _sharded_ops(kernel, mesh, axis, inner_backend, block_rows)
    axis = ops.axis_name  # a passed Mesh's own axis name wins (as above)
    inner = ops.inner()
    n = y.shape[0]
    nlam = n * lam
    Xp, yp, Bp = ops._shard_rows(X, y, B)
    mask = (jnp.arange(Xp.shape[0]) < n).astype(Xp.dtype)

    def local(X_blk: Array, y_blk: Array, B_blk: Array,
              m_blk: Array) -> tuple[Array, Array]:
        p = B_blk.shape[1]
        G = jax.lax.psum(B_blk.T @ B_blk, axis) + nlam * jnp.eye(
            p, dtype=B_blk.dtype)
        cG, lowG = jax.scipy.linalg.cho_factor(0.5 * (G + G.T))

        def precond(v_blk: Array) -> Array:
            Bv = jax.lax.psum(B_blk.T @ v_blk, axis)
            z = jax.scipy.linalg.cho_solve((cG, lowG), Bv)
            return m_blk * (v_blk - B_blk @ z) / nlam

        X_all = jax.lax.all_gather(X_blk, axis, tiled=True)   # (n_pad, dim)

        def matvec(v_blk: Array) -> Array:
            v_all = jax.lax.all_gather(v_blk, axis, tiled=True)
            return m_blk * inner.matvec(X_blk, X_all, v_all) + nlam * v_blk

        def dot(a: Array, b: Array) -> Array:
            return jax.lax.psum(jnp.vdot(a, b), axis)

        x = jnp.zeros_like(y_blk)
        r = y_blk - matvec(x)
        z = precond(r)
        pvec = z
        rz = dot(r, z)

        def body(carry, _):
            x, r, pvec, rz = carry
            Ap = matvec(pvec)
            alpha_step = rz / jnp.maximum(dot(pvec, Ap), 1e-300)
            x = x + alpha_step * pvec
            r = r - alpha_step * Ap
            z = precond(r)
            rz_new = dot(r, z)
            beta = rz_new / jnp.maximum(rz, 1e-300)
            pvec = z + beta * pvec
            return (x, r, pvec, rz_new), jnp.sqrt(dot(r, r))

        (x, r, _, _), res = jax.lax.scan(body, (x, r, pvec, rz), None,
                                         length=iters)
        return x, res

    fn = shard_map_norep(local, mesh=ops.mesh(),
                         in_specs=(P(axis, None), P(axis), P(axis, None),
                                   P(axis)),
                         out_specs=(P(axis), P()))
    alpha, res = fn(Xp, yp, Bp, mask)
    return PCGResult(alpha[:n], res)


# ------------------------------------------- first-class landmark-space PCG

class LandmarkPCG(NamedTuple):
    """Result of the landmark-space FALKON solve (``SOLVERS["falkon_pcg"]``)."""

    beta: Array        # (p,) / (p, k) landmark dual, in the solve dtype
    iters: int         # PCG iterations actually run (early stop counts)
    residuals: Array   # (iters,) relative residual ‖r‖/‖b‖ per iteration


def _coldot(u: Array, v: Array) -> Array:
    return jnp.sum(u * v, axis=0)


def _unpreconditioned(operands, r: Array) -> Array:
    return r


@partial(jax.jit, static_argnums=(0, 1))
def pcg_step(matvec, msolve, operands, bfloor: Array, x: Array, r: Array,
             pvec: Array, rz: Array) -> tuple:
    """One PCG iteration; returns ``(x, r, pvec, rz, rel)``.

    ``matvec`` and ``msolve`` are static: module-level functions, or ones
    cached per executor (:func:`sketch_normal_matvec`), so equal fits pass
    the very same objects. Every array they read — and every scalar that
    varies between fits, such as nλ — arrives in ``operands``, so the
    lowered step holds no array constant and compiles once per (operator,
    preconditioner, shapes): a second fit, a new λ or new rows reuse it,
    and the persistent compile cache can hold it.
    """
    tiny = float(jnp.finfo(x.dtype).tiny)
    Hp = matvec(operands, pvec)
    a = rz / jnp.maximum(_coldot(pvec, Hp), tiny)
    x = x + a * pvec
    r = r - a * Hp
    z = msolve(operands, r)
    rz_new = _coldot(r, z)
    bs = rz_new / jnp.maximum(rz, tiny)
    pvec = z + bs * pvec
    rel = jnp.max(jnp.sqrt(_coldot(r, r)) / bfloor)
    return x, r, pvec, rz_new, rel


def pcg_solve(matvec, b: Array, msolve=None, operands=None, *,
              tol: float = 1e-6, max_iters: int = 100
              ) -> tuple[Array, int, Array]:
    """Preconditioned conjugate gradients on an SPD operator.

    Generic engine behind both FALKON routes: ``matvec(operands, v)`` is
    any linear map v ↦ Hv (implicit backend-streamed kernel passes,
    accumulated p×p statistics, …) and ``msolve(operands, r)`` an optional
    preconditioner application r ↦ M⁻¹r (``None`` = unpreconditioned CG —
    kept callable so benchmarks can record both in the same run). Both are
    pure functions of the ``operands`` pytree and must be hashable and
    equal across calls (see :func:`pcg_step`). Multi-output RHS columns of
    shape (p, k) share each matvec, with per-column step sizes. The jitted
    :func:`pcg_step` runs once per iteration of a host-side loop that stops
    when max-over-columns ‖r‖/‖b‖ ≤ ``tol``. Denominators are floored at
    the dtype tiny so a converged (or zero) system never divides by 0.

    Returns ``(x, iters, residual_history)``.
    """
    msolve = msolve or _unpreconditioned
    tiny = float(jnp.finfo(b.dtype).tiny)
    bfloor = jnp.maximum(jnp.sqrt(_coldot(b, b)), tiny)
    x = jnp.zeros_like(b)
    r = b
    pvec = msolve(operands, r)
    rz = _coldot(r, pvec)
    # trace-time (auditor) fallback inf: no early stop, so the traced
    # solve unrolls the full ``max_iters`` — the worst case of any eager
    # run, which is exactly what the space-invariant audit must bound
    rel = concrete_float(jnp.max(jnp.sqrt(_coldot(r, r)) / bfloor),
                         math.inf)
    history = []
    it = 0
    while it < max_iters and rel > tol:
        # the first step at new shapes holds its compile (or its load from
        # the persistent cache), the rest one device iteration each (the
        # residual read waits for it)
        with jax.profiler.TraceAnnotation("solver.pcg_step", it=it):
            x, r, pvec, rz, rel_j = pcg_step(matvec, msolve, operands,
                                             bfloor, x, r, pvec, rz)
            rel = concrete_float(rel_j, math.inf)
        it += 1
        history.append(rel)
    # cast on the host: a device cast would compile once per history length
    return x, it, jnp.asarray(np.asarray(history, dtype=np.float32))


@lru_cache(maxsize=32)
def sketch_normal_matvec(ops: KernelOps):
    """v ↦ w ∘ gram_matvec(X, Z, w ∘ v) + nλ·Av over the operands ``X``,
    ``Z``, ``w``, ``A`` and ``nlam`` — one function per executor value
    (``KernelOps`` hash by configuration), so every fit through equal
    executors shares one compiled :func:`pcg_step`."""
    def matvec(operands, v: Array) -> Array:
        A, w = operands["A"], operands["w"]
        kv = ops.gram_matvec(operands["X"], operands["Z"], w * v)
        return w * kv.astype(A.dtype) + operands["nlam"] * (A @ v)
    return matvec


def stats_normal_matvec(operands, v: Array) -> Array:
    """v ↦ Gs·v + nλ·Av over the operands ``Gs``, ``A`` and ``nlam``."""
    return operands["Gs"] @ v + operands["nlam"] * (operands["A"] @ v)


def cholesky_msolve(operands, r: Array) -> Array:
    """r ↦ (L Lᵀ)⁻¹r: two p×p triangular solves against operand ``L``."""
    L = operands["L"]
    z = jax.scipy.linalg.solve_triangular(L, r, lower=True)
    return jax.scipy.linalg.solve_triangular(L, z, trans=1, lower=True)


def nystrom_pcg_preconditioner(W: Array, weights: Array, A: Array,
                               nlam: float, jitter: float) -> Array:
    """L with L Lᵀ = M = Ws·Ws + nλ·A — the FALKON preconditioner's factor,
    applied by :func:`cholesky_msolve`.

    With sketch weights w_j² = 1/(p·q_j) (``draw_columns``), Ws² is the
    importance-corrected unbiased estimate of CsᵀCs under ANY sampling
    distribution (uniform reduces it to the classic (n/p)²W² FALKON
    matrix), so M ≈ H = CsᵀCs + nλA and the PCG spectrum clusters at 1.
    M is SPD (A ⪰ nγI, the caller's ``regularized_penalty``) and factored
    once by the shared jittered Cholesky; application is two p×p
    triangular solves per iteration.
    """
    Ws = (W * weights[None, :]) * weights[:, None]
    M = Ws @ Ws + nlam * A
    return jittered_cholesky(M, jitter)


def _landmark_pcg(matvec, operands: dict, b: Array, W: Array,
                  weights: Array, n: int, lam: float, gamma: float,
                  jitter: float, precondition: bool, tol: float,
                  max_iters: int) -> LandmarkPCG:
    """The two FALKON routes' shared tail: the penalty A, nλ and the
    preconditioner's factor join ``operands``, then :func:`pcg_solve`."""
    A = regularized_penalty(W, weights, n, gamma)
    nlam = n * lam
    operands = dict(operands, A=A, nlam=jnp.asarray(nlam, A.dtype))
    msolve = None
    if precondition:
        operands["L"] = nystrom_pcg_preconditioner(W, weights, A, nlam,
                                                   jitter)
        msolve = cholesky_msolve
    beta, iters, res = pcg_solve(matvec, b, msolve, operands, tol=tol,
                                 max_iters=max_iters)
    return LandmarkPCG(beta, iters, res)


def falkon_pcg_krr(ops: KernelOps, X: Array, y: Array, Z: Array,
                   weights: Array, lam: float, gamma: float, *,
                   tol: float = 1e-6, max_iters: int = 100,
                   jitter: float = 1e-10,
                   precondition: bool = True) -> LandmarkPCG:
    """First-class FALKON: Nyström-preconditioned CG on the sketch's
    landmark-space normal equations.

    Solves (CsᵀCs + nλA)β = Csᵀy — the exact system ``nystrom_regularized``
    factors in closed form — without ever materializing Cs: the operator is
    applied as Hv = w ∘ gram_matvec(X, Z, w ∘ v) + nλ·Av, where
    ``ops.gram_matvec`` streams two kernel passes through whichever
    executor the config picked (dense xla, pallas tiles, streaming
    row-chunks, or mesh-sharded with a psum — they all compose). Live
    state is O(p) + one O(block·p) kernel tile; the preconditioner is
    :func:`nystrom_pcg_preconditioner` (skipped when
    ``precondition=False``, giving plain CG for the benchmark's
    iterations-to-tolerance comparison). Dtypes follow the ``Precision``
    policy via ``landmark_solve_dtypes``.
    """
    n = X.shape[0]
    _, sd = landmark_solve_dtypes(ops, Z.dtype)
    W = ops.cross(Z, Z).astype(sd)
    wgt = weights.astype(sd)
    ry = ops.rmatvec(X, Z, y)
    wcol = wgt.reshape((-1,) + (1,) * (ry.ndim - 1))
    b = wcol * ry.astype(sd)
    return _landmark_pcg(sketch_normal_matvec(ops), dict(X=X, Z=Z, w=wcol),
                         b, W, wgt, n, lam, gamma,
                         storage_floored_jitter(jitter, Z.dtype),
                         precondition, tol, max_iters)


def falkon_pcg_from_stats(W: Array, weights: Array, Gc: Array, bc: Array,
                          n: int, gamma: float, lam: float, *,
                          tol: float = 1e-6, max_iters: int = 100,
                          jitter: float = 1e-10,
                          precondition: bool = True) -> LandmarkPCG:
    """Chunked twin of :func:`falkon_pcg_krr`, off one-pass statistics.

    ``Gc`` = CsᵀCs and ``bc`` = Csᵀy arrive from the out-of-core
    accumulator (the *weighted*-column convention of
    ``nystrom_regularized_beta_from_stats``), so the PCG operator is the
    dense p×p map v ↦ ½(Gc+Gcᵀ)v + nλ·Av — the data was streamed exactly
    once regardless of iteration count, which strictly dominates
    re-streaming rows per CG iteration. All inputs are expected in the
    caller's solve dtype.
    """
    return _landmark_pcg(stats_normal_matvec, dict(Gs=0.5 * (Gc + Gc.T)),
                         bc, W, weights, n, lam, gamma, jitter,
                         precondition, tol, max_iters)
