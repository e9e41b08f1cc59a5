"""Pallas TPU kernel: fused ridge-leverage score evaluation (paper eq. 9).

Step 5 of the paper's algorithm computes, for every data point,
    l̃_i = B_i (BᵀB + nλI)^{-1} B_iᵀ
with B ∈ R^{n×p}. Given the precomputed p×p inverse M = (BᵀB + nλI)^{-1}
(O(p³), done once in XLA), the naive evaluation materializes B·M (another
n×p HBM round-trip). This kernel fuses it:

  grid = (n/bn, p/tp, p/tp); program (i, j, k) loads the B tiles
  B[i, j] and B[i, k] (each (bn, tp)) and the M tile M[j, k] (tp, tp),
  computes T = B[i, j]·M[j, k] on the MXU and adds rowsum(T ⊙ B[i, k])
  into row block i's output on the VPU — l̃_i = Σ_jk B_ij M_jk B_ik, with
  no n×p intermediate in HBM.

M is tiled along both axes, so the kernel's VMEM footprint is
O(bn·tp + tp²) whatever p is: it lowers for every p (a whole (p, p) M
block stops fitting VMEM at p = 2048 in f32). For p ≤ ``TILE_P`` the grid
is (n/bn, 1, 1) and each program reads its B row tile and all of M.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array
from jax.experimental import pallas as pl

DEFAULT_BN = 512
TILE_P = 512   # M tile edge for p > TILE_P (128-lane aligned)

_ZERO = np.int32(0)   # int32 block index: see rbf_block._ZERO


def _rls_kernel(bj_ref, m_ref, bk_ref, o_ref, *, acc):
    j, k = pl.program_id(1), pl.program_id(2)

    @pl.when((j == 0) & (k == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    t = jax.lax.dot_general(bj_ref[...].astype(acc), m_ref[...].astype(acc),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=acc)       # (bn, tp)
    o_ref[...] += jnp.sum(t * bk_ref[...].astype(acc), axis=-1,
                          keepdims=True)


@functools.partial(jax.jit, static_argnames=("bn", "interpret", "acc_dtype"))
def rls_scores_fused(B: Array, M: Array, *, bn: int = DEFAULT_BN,
                     interpret: bool = False,
                     acc_dtype: str | None = None) -> Array:
    """l̃ = rowwise B M Bᵀ ∈ R^n, fused. B: (n, p), M: (p, p) SPD inverse.

    Accumulates in float64 for float64 inputs (interpret-mode validation),
    float32 otherwise (the MXU path — bf16 B tiles ride it with f32
    accumulation). ``acc_dtype`` overrides the rule explicitly. M enters
    the kernel in the accumulation dtype: a wider M (the policy's p×p
    solve dtype) is rounded once here, as the tile would round it."""
    n, p = B.shape
    acc = (jnp.dtype(acc_dtype) if acc_dtype
           else jnp.float64 if B.dtype == jnp.float64 else jnp.float32)
    M = M.astype(acc)
    bn_ = min(bn, ((n + 7) // 8) * 8)
    tp = p if p <= TILE_P else TILE_P
    pad_n, pad_p = -n % bn_, -p % tp
    if pad_n or pad_p:
        B = jnp.pad(B, ((0, pad_n), (0, pad_p)))
    if pad_p:
        M = jnp.pad(M, ((0, pad_p), (0, pad_p)))
    nt, pt = B.shape[0] // bn_, B.shape[1] // tp
    out = pl.pallas_call(
        functools.partial(_rls_kernel, acc=acc),
        grid=(nt, pt, pt),
        in_specs=[
            pl.BlockSpec((bn_, tp), lambda i, j, k: (i, j)),
            pl.BlockSpec((tp, tp), lambda i, j, k: (j, k)),
            pl.BlockSpec((bn_, tp), lambda i, j, k: (i, k)),
        ],
        out_specs=pl.BlockSpec((bn_, 1), lambda i, j, k: (i, _ZERO)),
        out_shape=jax.ShapeDtypeStruct((B.shape[0], 1), acc),
        interpret=interpret,
    )(B, M, B)
    return out[:n, 0].astype(B.dtype)
