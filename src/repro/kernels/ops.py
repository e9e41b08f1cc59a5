"""Jit'd public wrappers over the Pallas kernels with CPU-interpret fallback.

On the validation platform (CPU) Pallas TPU kernels cannot be lowered to a
real mosaic custom-call, so every wrapper auto-enables ``interpret=True``
unless a TPU backend is present. On TPU the same call sites compile to the
real kernels. ``use_pallas=False`` (e.g. inside the 512-device dry-run,
where interpret mode under SPMD would be meaningless) routes to the jnp
reference, which XLA fuses well. Whether the kernels beat that fusion on
a TPU has not been measured yet; they are validated against ``ref`` in
tests and compiled for a described TPU v5e in ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import Array

from . import ref
from .flash_attention import flash_attention as _flash
from .rbf_block import kernel_block as _kernel_block
from .rls_scores import rls_scores_fused as _rls_fused
from .sparse_block import onehot_tile_fits
from .sparse_block import sparse_kernel_block as _sparse_kernel_block


def _needs_interpret() -> bool:
    """True off-TPU (Pallas TPU kernels can only interpret there).

    Deliberately NOT cached: the answer is re-derived from the *current*
    ``jax.default_backend()`` on every call, so tests (or runtimes) that
    simulate platforms are never pinned by whichever backend happened to be
    active at the first call. The check is a string compare — caching it
    bought nothing and froze the detection order.
    """
    return jax.default_backend() != "tpu"


def rbf_block(X: Array, Z: Array, *, bandwidth: float = 1.0,
              use_pallas: bool = True, acc_dtype: str | None = None) -> Array:
    if not use_pallas:
        return ref.rbf_block_ref(X, Z, bandwidth)
    return _kernel_block(X, Z, bandwidth=bandwidth, kind="rbf",
                         interpret=_needs_interpret(), acc_dtype=acc_dtype)


def linear_block(X: Array, Z: Array, *, use_pallas: bool = True,
                 acc_dtype: str | None = None) -> Array:
    if not use_pallas:
        return ref.linear_block_ref(X, Z)
    return _kernel_block(X, Z, kind="linear", interpret=_needs_interpret(),
                         acc_dtype=acc_dtype)


def poly_block(X: Array, Z: Array, *, degree: int = 2, scale: float = 1.0,
               offset: float = 1.0, use_pallas: bool = True,
               acc_dtype: str | None = None) -> Array:
    if not use_pallas:
        return ref.poly_block_ref(X, Z, degree, scale, offset)
    return _kernel_block(X, Z, kind="poly", degree=degree, scale=scale,
                         offset=offset, interpret=_needs_interpret(),
                         acc_dtype=acc_dtype)


def sparse_block(data: Array, indices: Array, indptr: Array, Z: Array, *,
                 kind: str = "rbf", bandwidth: float = 1.0, degree: int = 2,
                 scale: float = 1.0, offset: float = 1.0,
                 use_pallas: bool = True,
                 acc_dtype: str | None = None) -> Array:
    """CSR kernel block k(X_csr, Z) — the one sparse primitive behind
    every backend's CSR path.

    The contraction is picked by a written rule, never by catching a
    lowering error:

    * on a TPU, with ``use_pallas``, when the one-hot MXU tile fits its
      VMEM budget at this block's widths (``onehot_tile_fits``: nnz
      tile, row count, p and feature width d) → the Pallas one-hot tiles;
    * otherwise → the XLA take + segment-sum scan. That covers every
      non-TPU platform (interpreting the one-hot matmuls on CPU would be
      strictly slower than the fused XLA scan, which is itself
      densification-free) and TPU blocks too wide for the one-hots, such
      as hashed features at d in the 10⁵–10⁶ or 4096-row chunks.
    """
    p, d = Z.shape
    acc = jnp.dtype(acc_dtype) if acc_dtype else jnp.result_type(
        data.dtype, Z.dtype)
    pallas = (use_pallas and not _needs_interpret()
              and onehot_tile_fits(data.shape[0], indptr.shape[0] - 1, p, d,
                                   acc.itemsize))
    return _sparse_kernel_block(data, indices, indptr, Z, kind=kind,
                                bandwidth=bandwidth, degree=degree,
                                scale=scale, offset=offset,
                                use_pallas=pallas, interpret=False,
                                acc_dtype=acc_dtype)


def attention(q: Array, k: Array, v: Array, *, causal: bool = True,
              window: int = 0, scale: float = 0.0,
              use_pallas: bool = True) -> Array:
    if not use_pallas:
        return ref.attention_ref(q, k, v, scale=scale or None, causal=causal,
                                 window=window)
    return _flash(q, k, v, scale, causal, window,
                  interpret=_needs_interpret())


def rls_scores(B: Array, M: Array, *, use_pallas: bool = True,
               acc_dtype: str | None = None) -> Array:
    """Fused rowwise l̃_i = B_i M B_iᵀ (eq. 9 given M = (BᵀB + nλI)^{-1}).

    Shard-safe: also invoked per device as the body of the sharded
    backend's ``scores_given_gram`` (B is then the shard's row block and M
    comes from the psum'd global Gram) — ``shard_map_norep`` disables the
    replication check that pallas_call lacks a rule for.
    """
    if not use_pallas:
        return ref.rls_scores_ref(B, M)
    return _rls_fused(B, M, interpret=_needs_interpret(),
                      acc_dtype=acc_dtype)
