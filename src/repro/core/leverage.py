"""Exact λ-ridge leverage scores and the paper's fast O(np²) approximation.

Definition 1:   l_i(λ) = [K (K + nλ I)^{-1}]_ii = Σ_j σ_j/(σ_j + nλ) U_ij²
Effective dim:  d_eff(λ) = Σ_i l_i(λ) = Tr(K (K + nλ I)^{-1})
Max d.o.f.:     d_mof(λ) = n · max_i l_i(λ)            (Bach [2])

Fast approximation (paper §3.5 / Theorem 4):
  1. sample p landmarks with p_i = K_ii / Tr(K) (squared-length sampling),
  2. B with B Bᵀ = C W† Cᵀ (Cholesky of W, triangular solve against Cᵀ),
  3. l̃_i = B_iᵀ (BᵀB + nλ I)^{-1} B_i   — everything in dimension p.

Guarantees (Theorem 4, for p ≥ 8(Tr(K)/(nλε) + 1/6) log(n/ρ)):
  additive:        l_i(λ) − 2ε ≤ l̃_i ≤ l_i(λ)
  multiplicative:  ((σ_n − nλε)/(σ_n + nλε)) l_i(λ) ≤ l̃_i ≤ l_i(λ)
"""
from __future__ import annotations

import math
from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import Array

# jittered_cholesky moved to backends; imported here so existing
# ``from repro.core.leverage import jittered_cholesky`` callers keep working
from .backends import (KernelOps, jittered_cholesky, ops_for,
                       reference_leverage_scores)
from .kernels import Kernel
from .precision import (Precision, precision_independent_probs,
                        storage_floored_jitter)


# ---------------------------------------------------------------- exact path

def ridge_leverage_scores(K: Array, lam: float) -> Array:
    """Exact l_i(λ) = diag(K (K + nλI)^{-1}).  O(n³).

    Computed via a Cholesky solve rather than eigendecomposition: with
    A = K + nλI,  diag(K A^{-1}) = 1 − nλ · diag(A^{-1}).
    """
    n = K.shape[0]
    A = K + n * lam * jnp.eye(n, dtype=K.dtype)
    # diag(A^{-1})_i = ‖L^{-1} e_i‖² with A = L Lᵀ — same O(n³) as inv but
    # better conditioned, and consistent with krr_fit's Cholesky solve.
    Lchol = jnp.linalg.cholesky(A)
    V = jax.scipy.linalg.solve_triangular(Lchol, jnp.eye(n, dtype=K.dtype),
                                          lower=True)
    return 1.0 - n * lam * jnp.sum(V * V, axis=0)


def ridge_leverage_scores_eig(K: Array, lam: float) -> Array:
    """Definition-1 form through the eigendecomposition (oracle for tests)."""
    n = K.shape[0]
    sig, U = jnp.linalg.eigh(K)
    sig = jnp.maximum(sig, 0.0)
    w = sig / (sig + n * lam)
    return (U * U) @ w


def effective_dimension(K: Array, lam: float) -> Array:
    """d_eff(λ) = Tr(K (K + nλI)^{-1})."""
    return jnp.sum(ridge_leverage_scores(K, lam))


def max_degrees_of_freedom(K: Array, lam: float) -> Array:
    """Bach's d_mof(λ) = n ‖diag(K (K + nλI)^{-1})‖_∞."""
    return K.shape[0] * jnp.max(ridge_leverage_scores(K, lam))


def theorem3_sample_size(d_eff: float, n: int, beta: float = 1.0,
                         rho: float = 0.1) -> int:
    """p ≥ 8 (d_eff/β + 1/6) log(n/ρ)  (Theorem 3)."""
    return int(math.ceil(8.0 * (d_eff / beta + 1.0 / 6.0) * math.log(n / rho)))


def theorem4_sample_size(trace_K: float, n: int, lam: float, eps: float,
                         rho: float = 0.1) -> int:
    """p ≥ 8 (Tr(K)/(nλε) + 1/6) log(n/ρ)  (Theorem 4)."""
    return int(math.ceil(8.0 * (trace_K / (n * lam * eps) + 1.0 / 6.0)
                         * math.log(n / rho)))


# ------------------------------------------------------------ fast O(np²)

class FastLeverageResult(NamedTuple):
    scores: Array        # l̃_i, shape (n,)
    landmarks: Array     # sampled indices, shape (p,)
    B: Array | None      # (n, p) factor with B Bᵀ = L; None when the
    #                      backend streamed the score pass (never formed B)
    d_eff_estimate: Array
    row_sq: Array | None = None  # ‖B_i‖², populated by streamed passes


def _nystrom_factor(C: Array, W: Array, jitter: float, *,
                    solve_dtype=None) -> Array:
    """B such that B Bᵀ = C W† Cᵀ, via Cholesky of (W + jitter·tr(W)/p·I).

    Step 4 of the paper's algorithm: Cholesky on the p×p overlap W and a
    triangular solve against Cᵀ — O(p³ + np²). ``solve_dtype`` (a
    ``Precision.solve_for`` resolution) runs the factorization and the
    solve at that precision; B comes back in C's dtype either way, since
    it is O(n·p) model state. The jitter is floored per-dtype inside
    ``jittered_cholesky``.
    """
    # sub-f32 W carries O(eps_storage) rounding a wide solve can't undo —
    # floor the jitter at the storage dtype before any upcast
    Lchol = jittered_cholesky(
        W if solve_dtype is None else W.astype(solve_dtype),
        storage_floored_jitter(jitter, W.dtype))
    # B = C L^{-T}  =>  B Bᵀ = C (L Lᵀ)^{-1} Cᵀ = C Wj^{-1} Cᵀ
    Bt = jax.scipy.linalg.solve_triangular(Lchol, C.T.astype(Lchol.dtype),
                                           lower=True)
    return Bt.T.astype(C.dtype)


def _scores_from_factor(B: Array, lam: float, n: int) -> Array:
    """l̃_i = B_i (BᵀB + nλI)^{-1} B_iᵀ — the p-dimensional formula (eq. 9).

    Thin wrapper over the backend layer's reference evaluation; the pallas
    backend fuses the same formula through ``kernels.ops.rls_scores``."""
    return reference_leverage_scores(B, lam, n)


@partial(jax.jit, static_argnames=("p", "replace"))
def draw_landmarks(key: Array, probs: Array, p: int,
                   replace: bool = True) -> Array:
    """The Theorem-4 landmark draw, jitted per (n, p, replace) shape.

    The landmark set must not change with the pipeline precision — probs
    route through ``precision_independent_probs`` (the same shared draw
    convention as ``nystrom.draw_columns``). Jitting matters for the
    BLESS annealer: an eager weighted without-replacement ``choice`` costs
    tens of milliseconds in dispatch per stage — more than a small stage's
    whole score pass — while the jitted draw is cached per stage shape.
    """
    n = probs.shape[0]
    return jax.random.choice(key, n, shape=(p,), replace=replace,
                             p=precision_independent_probs(probs))


def fast_ridge_leverage(
    kernel: Kernel,
    X: Array,
    lam: float,
    p: int,
    key: Array,
    *,
    probs: Array | None = None,
    jitter: float = 1e-10,
    replace: bool = True,
    ops: KernelOps | None = None,
) -> FastLeverageResult:
    """The paper's §3.5 algorithm, end-to-end, never materializing K.

    By default samples with the Theorem-4 distribution p_i = K_ii / Tr(K)
    (squared length / diagonal sampling). Runs in O(np² + p³).

    ``replace=False`` draws a duplicate-free landmark set (weighted,
    without replacement) — callers whose ``probs`` concentrate on few rows
    (the BLESS annealer's late stages) need this: a repeated landmark makes
    the overlap W exactly singular, which the streamed f32 score pass
    cannot absorb (it solves the accumulated CᵀC through L_c⁻¹, so the
    jittered near-null directions amplify storage rounding past nλ).

    ``ops`` selects the kernel execution backend (``repro.core.backends``);
    ``None`` resolves ``"auto"`` for the current platform. Backends that
    fuse the score pass (``streaming`` chunks it so C and B never
    materialize at all; ``sharded`` runs it under ``shard_map`` with one
    p×p collective, no (n, p) block on any single device) return their
    scores through ``score_pass`` — the result then carries ``B=None``
    plus the ``row_sq`` norms instead.
    """
    if ops is None:
        ops = ops_for(kernel)
    n = X.shape[0]
    diag = kernel.diag(X)
    if probs is None:
        probs = diag / jnp.sum(diag)
    idx = draw_landmarks(key, probs, p, replace)
    if ops.streams_score_pass:
        scores, row_sq = ops.score_pass(X, idx, lam, jitter)
        return FastLeverageResult(scores, idx, None, jnp.sum(scores), row_sq)
    try:
        scores, B = _dense_score_pass(ops)(X, idx, lam, jitter)
    except TypeError:
        # duck-typed ops (the documented protocol surface) may be
        # unhashable — run the same body eagerly
        scores, B = _dense_pass_body(ops, X, idx, lam, jitter)
    return FastLeverageResult(scores, idx, B, jnp.sum(scores))


def _dense_pass_body(ops, X: Array, idx: Array, lam, jitter) -> tuple:
    """The dense (column-materializing) score pass: C → W → B → scores."""
    C = ops.columns(X, idx)                     # (n, p): only p columns of K
    W = C[idx, :]                               # (p, p) overlap
    # duck-typed ops may not carry a precision policy — use the default
    pr = getattr(ops, "precision", None) or Precision()
    B = _nystrom_factor(C, W, jitter, solve_dtype=pr.solve_for(C.dtype))
    return ops.leverage_scores(B, lam, X.shape[0]), B


@lru_cache(maxsize=32)
def _dense_score_pass(ops):
    """``_dense_pass_body`` jitted with ``ops`` closed over, cached per
    ops value (frozen dataclasses hash by configuration, so equal
    pipelines share one jit cache across instances). λ and jitter stay
    traced arguments — a new λ never recompiles, only a new (n, p) shape
    does. This is what keeps a BLESS stage's cost at its FLOPs: eagerly,
    the ~15 dispatches here dwarf a small stage's whole score pass."""
    def score_pass(X, idx, lam, jitter):
        return _dense_pass_body(ops, X, idx, lam, jitter)
    return jax.jit(score_pass)


@partial(jax.jit, static_argnums=(3,))
def fast_ridge_leverage_from_columns(C: Array, idx: Array, lam: float,
                                     n: int, jitter: float = 1e-10) -> Array:
    """Jit-friendly core: scores from precomputed columns (used distributed)."""
    W = C[idx, :]
    B = _nystrom_factor(C, W, jitter)
    return _scores_from_factor(B, lam, n)
