"""CSR kernel blocks: nnz-tiled take+segment-sum contraction.

The sparse seam computes k(X_csr, Z) — X a CSR row block, Z a *dense*
(p, d) landmark block — without ever materializing the dense
``(n_rows, d)`` form of X. Everything reduces to one primitive, the
sparse cross product ``X @ Zᵀ``:

* ``linear``: the cross product itself;
* ``poly``: ``(cross / scale + offset)^degree`` elementwise;
* ``rbf``: the ‖x−z‖² expansion ``‖x‖² + ‖z‖² − 2·cross`` over the same
  inner products, with ``‖x‖²`` a segment-sum of ``data²``.

The contraction walks the flat nnz stream in fixed tiles: per tile it
gathers the needed landmark columns (``take`` along d), scales by the
values, and scatter-adds into the (n_rows, p) output via ``segment_sum``
over row ids recovered from ``indptr`` by ``searchsorted``. Peak live
intermediate is the (tile, p) gather with tile ≤ max(n_rows, MIN_TILE),
so the whole block stays within nnz + O(n_rows·p) — the bound
``sparse_cell_bound`` derives and ``repro.analysis`` audits.

A Pallas TPU variant expresses the same tile as two MXU one-hot
matmuls (column gather, row scatter) with ``@pl.when``-guarded output
initialization. It runs only on a TPU and only where its VMEM estimate
fits (``onehot_tile_fits``); every other call site uses the XLA
reference (see ``kernels.ops.sparse_block``).

Zero-valued structural padding is harmless by construction: padded nnz
slots carry ``data == 0`` and padded tail rows get row id ``n_rows``,
which both ``segment_sum`` and the one-hot scatter drop.

This module depends on jax only (no ``repro`` imports): it sits below
both ``repro.data.sparse`` and ``repro.core.kernels`` in the layering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array
from jax.experimental import pallas as pl

__all__ = [
    "MIN_TILE", "ONEHOT_VMEM_BUDGET", "onehot_tile_fits", "sparse_tile",
    "sparse_cell_bound", "sparse_row_ids",
    "sparse_row_sqnorms", "sparse_cross", "sparse_kernel_block",
]

# floor on the nnz tile: below this the scan step count dominates; the
# tile-sized gather it implies is a constant O(MIN_TILE·p) ≈ one MXU pass
MIN_TILE = 512

# Pallas lane width — the TPU tile's minor dimension granularity
_LANE = 128

# VMEM the one-hot tile may plan for: half of the 16 MiB a Mosaic kernel
# may use by default on TPU v5e, leaving the compiler room for its own
# temporaries (``onehot_tile_fits``)
ONEHOT_VMEM_BUDGET = 8 * 2**20

_ZERO = np.int32(0)   # int32 block index (x64-safe, see rbf_block)


def sparse_tile(nnz_cap: int, n_rows: int) -> int:
    """The nnz tile width for a CSR block with ``nnz_cap`` stored values
    over ``n_rows`` rows: large enough to amortize the scan, but capped
    at ``max(n_rows, MIN_TILE)`` so the per-tile (tile, p) gather never
    exceeds O(n_rows·p) plus a hardware-sized constant."""
    return max(1, min(int(nnz_cap), max(int(n_rows), MIN_TILE)))


def sparse_cell_bound(nnz_cap: int, n_rows: int, p: int, d: int) -> int:
    """``MaxIntermediate`` bound for one sparse chunk step at ``p``
    landmarks over ``d`` features: the padded nnz stream, the (tile, p)
    gather, the (n_rows, p) block, the p×p core and the (p, d) landmark
    algebra — and *strictly less* than the dense chunk ``n_rows·d`` the
    sparse path exists to avoid (callers assert that separation)."""
    tile = sparse_tile(nnz_cap, n_rows)
    padded = nnz_cap + (-nnz_cap) % tile
    return max(padded + tile, tile * p, (n_rows + 1) * p,
               (p + 1) * p, (p + 1) * d) + 1


def sparse_row_ids(indptr: Array, nnz: int) -> Array:
    """Row id of every slot in the flat nnz stream, from the CSR row
    pointer: slot k lives in row i iff indptr[i] ≤ k < indptr[i+1]
    (``side='right'`` lands empty rows correctly). Slots at or beyond
    ``indptr[-1]`` — structural padding — map to ``n_rows``, an
    out-of-range segment that every consumer drops."""
    k = jnp.arange(nnz, dtype=jnp.int32)
    return (jnp.searchsorted(indptr, k, side="right") - 1).astype(jnp.int32)


def sparse_row_sqnorms(data: Array, indptr: Array, *,
                       acc_dtype=None) -> Array:
    """Per-row ‖x_i‖² of a CSR block — a segment-sum of ``data²`` (the
    rbf diagonal feed and the ‖x‖² term of the rbf expansion). Returned
    in the data dtype after accumulating in ``acc_dtype``."""
    n_rows = indptr.shape[0] - 1
    acc = jnp.dtype(acc_dtype) if acc_dtype is not None else data.dtype
    rows = sparse_row_ids(indptr, data.shape[0])
    sq = data.astype(acc) * data.astype(acc)
    return jax.ops.segment_sum(sq, rows, num_segments=n_rows
                               ).astype(data.dtype)


def _sparse_cross_ref(data: Array, indices: Array, rows: Array, Z: Array,
                      n_rows: int, tile: int, acc) -> Array:
    """XLA reference contraction: scan over nnz tiles, per tile a column
    gather from Z (axis-1 take, so no transposed (d, p) copy of the
    landmark block is ever live) and a segment-sum row scatter."""
    steps = data.shape[0] // tile
    p = Z.shape[0]

    def step(carry, t):
        dat, col, row = t
        taken = jnp.take(Z, col, axis=1).astype(acc)        # (p, tile)
        part = (taken * dat.astype(acc)[None, :]).T          # (tile, p)
        return carry + jax.ops.segment_sum(
            part, row, num_segments=n_rows), None

    init = jnp.zeros((n_rows, p), dtype=acc)
    out, _ = jax.lax.scan(step, init, (data.reshape(steps, tile),
                                       indices.reshape(steps, tile),
                                       rows.reshape(steps, tile)))
    return out


def _pallas_tile_body(d_ref, c_ref, r_ref, z_ref, o_ref, *, acc,
                      n_rows: int, n_cols: int):
    """One nnz tile as two MXU passes: a one-hot column matmul gathers
    landmark columns, a one-hot row matmul scatter-adds into the output
    block. Output is zeroed on the first tile and accumulated after.

    The nnz stream stays on the lane axis throughout — the one-hots are
    built transposed, (d, tile) and (n_rows, tile), so no vector is ever
    relaid from lanes to sublanes."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    dat = d_ref[...].astype(acc)                              # (1, tile)
    tile = dat.shape[1]
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (n_cols, tile), 0)
    onehot_c = (col_iota == c_ref[...]).astype(acc)           # (d, tile)
    g = jax.lax.dot_general(z_ref[...].astype(acc), onehot_c,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=acc) * dat  # (p, tile)
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (n_rows, tile), 0)
    onehot_r = (row_iota == r_ref[...]).astype(acc)           # (n, tile)
    o_ref[...] += jax.lax.dot_general(onehot_r, g,
                                      (((1,), (1,)), ((), ())),
                                      preferred_element_type=acc
                                      ).astype(o_ref.dtype)


def onehot_tile_fits(nnz_cap: int, n_rows: int, p: int, d: int,
                     itemsize: int = 4) -> bool:
    """Whether the one-hot MXU tile fits ``ONEHOT_VMEM_BUDGET`` for a CSR
    block of ``nnz_cap`` slots over ``n_rows`` rows against (p, d)
    landmarks — the rule ``kernels.ops.sparse_block`` selects the tile
    by on a TPU. The estimate counts the double-buffered (p, d) landmark
    block and (n_rows, p) output block, the three double-buffered
    (1, tile) nnz streams (each padded to 8 sublanes), and the live
    one-hots, their iotas and the (p, tile) gather. Both one-hots grow
    with the feature width d and the block's row count, so hashed
    feature widths (d in the 10⁵–10⁶) take the XLA take + segment-sum
    contraction instead."""
    tile = -(-sparse_tile(nnz_cap, n_rows) // _LANE) * _LANE
    vmem = itemsize * (2 * p * d + 2 * n_rows * p + 3 * 2 * 8 * tile
                       + 2 * (d + n_rows) * tile + p * tile + n_rows * p)
    return vmem <= ONEHOT_VMEM_BUDGET


def _sparse_cross_pallas(data: Array, indices: Array, rows: Array,
                         Z: Array, n_rows: int, tile: int, acc,
                         interpret: bool) -> Array:
    steps = data.shape[0] // tile
    p, d = Z.shape
    # (steps, 1, tile): each program's nnz slice is a (1, tile) block
    # spanning the array's two minor dims whole, as Mosaic's 8x128 rule
    # allows; a (1, tile) block of a (steps, tile) array does not lower
    shaped = [a.reshape(steps, 1, tile) for a in (data, indices, rows)]
    body = functools.partial(_pallas_tile_body, acc=acc, n_rows=n_rows,
                             n_cols=d)
    stream = pl.BlockSpec((None, 1, tile), lambda i: (i, _ZERO, _ZERO))
    return pl.pallas_call(
        body,
        grid=(steps,),
        in_specs=[stream, stream, stream,
                  pl.BlockSpec((p, d), lambda i: (_ZERO, _ZERO))],
        out_specs=pl.BlockSpec((n_rows, p), lambda i: (_ZERO, _ZERO)),
        out_shape=jax.ShapeDtypeStruct((n_rows, p), acc),
        interpret=interpret,
    )(*shaped, Z)


def sparse_cross(data: Array, indices: Array, indptr: Array, Z: Array, *,
                 acc_dtype=None, use_pallas: bool = False,
                 interpret: bool = False) -> Array:
    """``X_csr @ Zᵀ`` → (n_rows, p), never densifying X. Accumulates in
    ``acc_dtype`` (default: the result dtype), returns in
    ``result_type(data, Z)``. ``use_pallas`` selects the MXU one-hot
    tiles (lane-aligned); the default is the XLA scan reference."""
    n_rows = indptr.shape[0] - 1
    nnz = data.shape[0]
    out_dtype = jnp.result_type(data.dtype, Z.dtype)
    acc = jnp.dtype(acc_dtype) if acc_dtype is not None else out_dtype
    rows = sparse_row_ids(indptr, nnz)
    tile = sparse_tile(nnz, n_rows)
    if use_pallas:
        tile = -(-tile // _LANE) * _LANE
    pad = (-nnz) % tile
    if pad:
        data = jnp.pad(data, (0, pad))
        indices = jnp.pad(indices, (0, pad))
        rows = jnp.pad(rows, (0, pad), constant_values=n_rows)
    if use_pallas:
        out = _sparse_cross_pallas(data, indices, rows, Z, n_rows, tile,
                                   acc, interpret)
    else:
        out = _sparse_cross_ref(data, indices, rows, Z, n_rows, tile, acc)
    return out.astype(out_dtype)


def sparse_kernel_block(data: Array, indices: Array, indptr: Array,
                        Z: Array, *, kind: str = "rbf",
                        bandwidth: float = 1.0, degree: int = 2,
                        scale: float = 1.0, offset: float = 1.0,
                        acc_dtype=None, use_pallas: bool = False,
                        interpret: bool = False) -> Array:
    """Full kernel block k(X_csr, Z) for ``kind`` ∈ {rbf, linear, poly},
    assembled from the sparse cross product (module docstring). Padded
    tail rows (zero nnz) evaluate to exactly k(0, z) — the same value
    the dense executors produce for zero-padded rows, which keeps
    chunked sparse fits on the shared masking semantics."""
    out_dtype = jnp.result_type(data.dtype, Z.dtype)
    acc = jnp.dtype(acc_dtype) if acc_dtype is not None else out_dtype
    cross = sparse_cross(data, indices, indptr, Z, acc_dtype=acc,
                         use_pallas=use_pallas, interpret=interpret)
    if kind == "linear":
        return cross
    if kind == "poly":
        c = cross.astype(acc) / scale + offset
        return (c ** degree).astype(out_dtype)
    if kind == "rbf":
        row_sq = sparse_row_sqnorms(data, indptr,
                                    acc_dtype=acc).astype(acc)
        zc = Z.astype(acc)
        zz = jnp.sum(zc * zc, axis=1)
        d2 = jnp.maximum(row_sq[:, None] + zz[None, :]
                         - 2.0 * cross.astype(acc), 0.0)
        return jnp.exp(-d2 / (2.0 * bandwidth * bandwidth)
                       ).astype(out_dtype)
    raise ValueError(f"unknown sparse kernel kind: {kind!r}")
