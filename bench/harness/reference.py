"""Plain reference of the sketched-KRR fit: kernel blocks and their
products in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, in row blocks so that no
(n, p) array is held; the p×p algebra in float64 NumPy on the host.

It imports nothing of the program. From a fit it takes only the row
indices of the Theorem-3 draw (``model.sample().idx``), a discrete random
choice; the weights, factors, scores and duals it computes itself.

At FALKON's λ = 1e-6 the normal equations (CsᵀCs + nλA)β = Csᵀy have a
condition number near 1e11, beyond what float32 can factor. So each solve
runs in the basis P = L⁻¹ of a landmark-space Cholesky factor L Lᵀ, made
in float64 on the host: the blocks B = K·Pᵀ are well scaled, their Gram
BᵀB is accumulated in float32, and the small system BᵀB + nλI is solved
in float64. The answer does not depend on the basis; the basis only
keeps the rounding of the float32 products away from the solution.

* scores (Theorem 4, eq. 9, at λε): landmarks Z_s = X[idx_s] drawn with
  the fit's own key discipline (``key(seed)`` → sampler key → score-pass
  key, uniform because the RBF diagonal is 1), W = k(Z_s, Z_s) shifted by
  the relative jitter δ·(tr W/p + 1)·I, L Lᵀ = W, B = k(X, Z_s) L⁻ᵀ,
  l_i = B_i (BᵀB + nλεI)⁻¹ B_iᵀ;
* predictions (footnote-4 regularized Nyström, γ = λ): with
  w_j = 1/√(p·q_j), q = l/Σl at the drawn rows Z = X[idx],
  A = diag(w) k(Z, Z) diag(w) + nγI = L Lᵀ, F = k(X, Z)·diag(w)·L⁻ᵀ,
  f(x) = (k(x, Z)·w) L⁻ᵀ (FᵀF + nλI)⁻¹ Fᵀy.

Both the closed-form and the preconditioned-CG solvers converge to that f.

This is the reference of the RBF configurations: a configuration names
it by its ``reference`` key, and the drivers call it through the
interface every reference module has (``harness.cells.REFERENCE_API``):
``scores_and_predictions``, ``rel_rms``, ``max_gap`` and ``KERNELS``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import scipy.linalg as sl

# Relative jitter of the Theorem-4 landmark Cholesky: √eps of float32,
# the smallest shift that stays representable against an O(1) diagonal.
JITTER_REL = float(np.sqrt(np.finfo(np.float32).eps))
BLOCK = 2**14            # rows per reference block
# the uniform score landmarks below rely on the RBF kernel's unit diagonal
KERNELS = ("rbf",)


def rbf(A, B, bandwidth: float):
    d2 = (jnp.sum(A * A, 1)[:, None] + jnp.sum(B * B, 1)[None, :]
          - 2.0 * A @ B.T)
    return jnp.exp(-jnp.maximum(d2, 0.0) / (2.0 * bandwidth**2))


def score_landmarks(seed: int, n: int, p: int):
    """The score pass's landmark rows for a fit with ``SketchConfig.seed
    = seed``: uniform draws with replacement (the RBF diagonal is 1)."""
    k_sample, _ = jax.random.split(jax.random.key(seed))
    k_scores, _ = jax.random.split(k_sample)
    probs = jnp.full((n,), 1.0 / n, jnp.float32)
    return jax.random.choice(
        k_scores, n, shape=(p,), replace=True,
        p=probs.astype(jax.dtypes.canonicalize_dtype(jnp.float64)))


def _blocks(n: int):
    return [(s, min(s + BLOCK, n)) for s in range(0, n, BLOCK)]


@jax.jit
def _basis_block(Xb, Z, scale, Pt, bandwidth):
    with jax.default_matmul_precision("highest"):
        return (rbf(Xb, Z, bandwidth) * scale[None, :]) @ Pt


@jax.jit
def _gram_block(Xb, Z, scale, Pt, yb, bandwidth):
    with jax.default_matmul_precision("highest"):
        Bb = _basis_block(Xb, Z, scale, Pt, bandwidth)
        return Bb.T @ Bb, Bb.T @ yb


@jax.jit
def _score_rows(Xb, Z, Pt, M, bandwidth):
    with jax.default_matmul_precision("highest"):
        Bb = _basis_block(Xb, Z, jnp.ones(Z.shape[0], jnp.float32), Pt,
                          bandwidth)
        return jnp.sum((Bb @ M) * Bb, axis=1)


def _gram(Z, bandwidth: float) -> np.ndarray:
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(rbf, static_argnums=2)(Z, Z, bandwidth),
                          np.float64)


def _inverse_factor(A: np.ndarray) -> np.ndarray:
    """P = L⁻¹ for L Lᵀ = A, in float64, handed to the chip as float32."""
    L = np.linalg.cholesky(0.5 * (A + A.T))
    P, info = sl.lapack.dtrtri(L, lower=1)
    if info != 0:
        raise np.linalg.LinAlgError(f"dtrtri: {info}")
    return jnp.asarray(P.T, jnp.float32)        # Pᵀ, applied on the right


def _stats(X, y, Z, scale, Pt, bandwidth: float):
    n, p = X.shape[0], Z.shape[0]
    G = jnp.zeros((p, p), jnp.float32)
    b = jnp.zeros((p,), jnp.float32)
    for s, e in _blocks(n):
        Gb, bb = _gram_block(X[s:e], Z, scale, Pt, y[s:e], bandwidth)
        G, b = G + Gb, b + bb
    return np.asarray(G, np.float64), np.asarray(b, np.float64)


def scores(X, idx, lam: float, bandwidth: float) -> np.ndarray:
    """Theorem-4 scores l̃ of every row of ``X`` at ``lam`` (= λε)."""
    n, p = X.shape[0], idx.shape[0]
    Z = X[idx]
    W = _gram(Z, bandwidth)
    W = W + JITTER_REL * (np.trace(W) / p + 1.0) * np.eye(p)
    Pt = _inverse_factor(W)
    G, _ = _stats(X, jnp.zeros((n,), jnp.float32), Z,
                  jnp.ones((p,), jnp.float32), Pt, bandwidth)
    H = 0.5 * (G + G.T) + n * lam * np.eye(p)
    M = sl.cho_solve(sl.cho_factor(H, lower=True), np.eye(p))
    M = jnp.asarray(0.5 * (M + M.T), jnp.float32)
    out = [np.asarray(_score_rows(X[s:e], Z, Pt, M, bandwidth))
           for s, e in _blocks(n)]
    return np.concatenate(out)


def predict(X, y, Xt, idx, ref_scores, lam: float, bandwidth: float):
    """Held-out predictions of the regularized Nyström fit at the drawn
    rows ``idx``, with sketch weights from ``ref_scores``."""
    n, p = X.shape[0], idx.shape[0]
    q = np.asarray(ref_scores, np.float64) / np.sum(ref_scores)
    w = 1.0 / np.sqrt(p * q[np.asarray(idx)])
    Z = X[idx]
    A = (_gram(Z, bandwidth) * w[None, :]) * w[:, None] + n * lam * np.eye(p)
    Pt = _inverse_factor(A)
    w32 = jnp.asarray(w, jnp.float32)
    G, b = _stats(X, y, Z, w32, Pt, bandwidth)
    H = 0.5 * (G + G.T) + n * lam * np.eye(p)
    u = sl.cho_solve(sl.cho_factor(H, lower=True), b)
    Bt = np.asarray(_basis_block(Xt, Z, w32, Pt, bandwidth), np.float64)
    return Bt @ u


def scores_and_predictions(config: dict, seed: int, X, y, Xt, idx):
    """``(scores, predictions)`` of the fit with ``SketchConfig.seed =
    seed`` whose Theorem-3 draw was ``idx``: the Theorem-4 scores of every
    row of ``X`` and the predictions at the held-out rows ``Xt``."""
    idx_s = score_landmarks(seed, X.shape[0], config["p_scores"])
    ref_scores = scores(X, idx_s, config["lam"] * config["eps"],
                        config["bandwidth"])
    return ref_scores, predict(X, y, Xt, idx, ref_scores, config["lam"],
                               config["bandwidth"])


def rel_rms(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def max_gap(a, b) -> float:
    """Largest |a − b| over the RMS of ``b``: one wrong answer shows."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.sqrt(np.mean(b * b)))
