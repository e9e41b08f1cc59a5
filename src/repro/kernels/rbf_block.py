"""Pallas TPU kernel: tiled kernel-column computation C = k(X, Z).

This is the FLOP hot-spot of the paper's pipeline: forming the n×p sampled
column block C = K[:, I] costs O(n·p·d) kernel evaluations (§3.5 step 2) and
dominates the O(np²) algorithm at large d. On TPU we tile it for the MXU:

  grid = (n/bn, p/bp); each program brings an X row-tile (bn, d) and a
  Z landmark-tile (bp, d) into VMEM, runs the cross term on the MXU
  (jnp.dot, preferred_element_type=f32), fuses the ‖x‖², ‖z‖² rank-1
  corrections and the exp on the VPU, and writes the (bn, bp) C-tile.

Nothing n×n is ever materialized — the TPU translation of the paper's
"only the relevant columns of K are computed" property.

Supported kernels: rbf (default), linear (skips the exp/sq-dist fusion),
poly ((x·z/scale + offset)^degree, fused on the VPU).

Accumulation dtype follows the input: float64 inputs accumulate in float64
(interpret-mode/CPU validation only, where the backend parity suite demands
1e-10 agreement with the dense reference — Mosaic has no f64, and the
``pallas`` executor never sends f64 blocks to a TPU); everything narrower — f32 and
bf16 alike — accumulates in float32 as the MXU does, and the output tile is
written back in the input dtype (bf16 in ⇒ bf16 blocks, f32 arithmetic).
``acc_dtype`` overrides that rule explicitly when a precision policy wants
a wider accumulator than the default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array
from jax.experimental import pallas as pl


DEFAULT_BN = 256   # X rows per tile   (8-sublane aligned)
DEFAULT_BP = 128   # landmarks per tile (128-lane aligned)

# Block indices must be int32 for Mosaic: a bare Python ``0`` in an index
# map is traced as int64 when ``jax_enable_x64`` is on, and Mosaic then
# fails to legalize the index function's return.
_ZERO = np.int32(0)


def _acc_dtype(dtype) -> jnp.dtype:
    return jnp.float64 if dtype == jnp.float64 else jnp.float32


def _cross_tile(x_ref, z_ref, acc):
    x = x_ref[...].astype(acc)                    # (bn, d)
    z = z_ref[...].astype(acc)                    # (bp, d)
    cross = jax.lax.dot_general(                  # MXU: (bn, bp)
        x, z, (((1,), (1,)), ((), ())), preferred_element_type=acc)
    return x, z, cross


def _rbf_block_kernel(x_ref, z_ref, o_ref, *, two_h2: float, acc):
    x, z, cross = _cross_tile(x_ref, z_ref, acc)
    xx = jnp.sum(x * x, axis=-1)[:, None]
    zz = jnp.sum(z * z, axis=-1)[None, :]
    d2 = jnp.maximum(xx + zz - 2.0 * cross, 0.0)
    o_ref[...] = jnp.exp(-d2 / two_h2).astype(o_ref.dtype)


def _linear_block_kernel(x_ref, z_ref, o_ref, *, acc):
    _, _, cross = _cross_tile(x_ref, z_ref, acc)
    o_ref[...] = cross.astype(o_ref.dtype)


def _poly_block_kernel(x_ref, z_ref, o_ref, *, degree: int, scale: float,
                       offset: float, acc):
    _, _, cross = _cross_tile(x_ref, z_ref, acc)
    o_ref[...] = ((cross / scale + offset) ** degree).astype(o_ref.dtype)


def _pad_to(a: Array, size: int, axis: int) -> Array:
    pad = -a.shape[axis] % size
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


@functools.partial(jax.jit,
                   static_argnames=("bandwidth", "kind", "degree", "scale",
                                    "offset", "bn", "bp", "interpret",
                                    "acc_dtype"))
def kernel_block(X: Array, Z: Array, *, bandwidth: float = 1.0,
                 kind: str = "rbf", degree: int = 2, scale: float = 1.0,
                 offset: float = 1.0, bn: int = DEFAULT_BN,
                 bp: int = DEFAULT_BP, interpret: bool = False,
                 acc_dtype: str | None = None) -> Array:
    """C = k(X, Z) ∈ R^{n×p}, tiled (bn, d)×(bp, d) → (bn, bp) in VMEM.

    ``acc_dtype`` (a dtype name) overrides the default accumulation rule
    (f64 in ⇒ f64, else f32); the output stays in the input dtype.
    """
    n, d = X.shape
    p = Z.shape[0]
    bn_ = min(bn, max(_next_multiple(n, 8), 8))
    bp_ = min(bp, max(_next_multiple(p, 128), 128))
    Xp = _pad_to(X, bn_, 0)
    Zp = _pad_to(Z, bp_, 0)
    grid = (Xp.shape[0] // bn_, Zp.shape[0] // bp_)
    acc = jnp.dtype(acc_dtype) if acc_dtype else _acc_dtype(X.dtype)

    if kind == "rbf":
        body = functools.partial(_rbf_block_kernel,
                                 two_h2=2.0 * bandwidth**2, acc=acc)
    elif kind == "linear":
        body = functools.partial(_linear_block_kernel, acc=acc)
    elif kind == "poly":
        body = functools.partial(_poly_block_kernel, degree=degree,
                                 scale=scale, offset=offset, acc=acc)
    else:
        raise ValueError(f"unsupported kind {kind!r}")

    out = pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn_, d), lambda i, j: (i, _ZERO)),
            pl.BlockSpec((bp_, d), lambda i, j: (j, _ZERO)),
        ],
        out_specs=pl.BlockSpec((bn_, bp_), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Xp.shape[0], Zp.shape[0]), X.dtype),
        interpret=interpret,
    )(Xp, Zp)
    return out[:n, :p]


def _next_multiple(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
