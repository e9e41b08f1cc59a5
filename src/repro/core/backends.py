"""KernelOps: pluggable tiled executors for every kernel-matrix touch.

The paper's pipeline only ever needs p columns of K — "it can be applied to
the matrix of feature vectors, without having to form the full kernel
matrix" — so all kernel evaluation in this repo flows through one seam, a
``KernelOps`` object, instead of scattered dense ``kernel.gram`` calls.
Samplers, solvers, ``SketchedKRR.predict``/``predict_batched`` and the
``KRRServeEngine`` all take their kernel blocks from the backend configured
on ``SketchConfig`` (``backend=``/``block_rows=``).

The protocol (all shapes: X (n, d), Z (p, d), B (n, p)):

  ``columns(X, idx)``        C = K[:, idx] ∈ R^{n×p} — the §3.5 column block.
  ``cross(X_test, Z)``       k(X_test, Z) ∈ R^{m×p} — test/landmark block.
  ``matvec(X, Z, v)``        k(X, Z) @ v — implicit-C product (serving path).
  ``rmatvec(X, Z, v)``       k(X, Z)ᵀ @ v — implicit-Cᵀ product.
  ``leverage_scores(B,λ,n)`` l̃_i = B_i (BᵀB + nλI)^{-1} B_iᵀ — fused eq. (9).

Registered backends:

  ``xla``        the dense reference — one fused XLA op per block; bitwise
                 the behaviour of the pre-backend code. Direct
                 ``kernel.gram`` call sites live ONLY here.
  ``pallas``     routes rbf/linear/poly blocks to the tiled Pallas TPU
                 kernels in ``repro.kernels`` (``kernel_block``,
                 ``rls_scores_fused``); interpret-mode on CPU, real mosaic
                 kernels on TPU. Kernels without a tiled body (bernoulli)
                 fall back to the dense formula per-block.
  ``streaming``  row-chunked ``lax.map``/``lax.scan`` over ``block_rows``-
                 sized X tiles: every *compute* intermediate is
                 O(block_rows·p), and the Theorem-4 score pass
                 (``score_pass``) runs in two streamed passes that never
                 materialize C or B at all. (A fit's column sketch is
                 still returned whole — it IS the O(n·p) model state —
                 only the transient working set shrinks; matvec/rmatvec
                 and ``score_pass`` are the fully implicit paths.)
  ``sharded``    mesh-aware SPMD execution: X rows are sharded over a
                 ``data`` mesh axis with ``shard_map``, every per-shard
                 block is produced by a per-shard *inner* executor
                 (``inner_backend``: xla | pallas | streaming — the tiles
                 above compose under the shard), and every cross-device
                 collective is p-sized: one p×p ``psum`` of BᵀB for the
                 fused Theorem-4 score pass, Fᵀv / FᵀF inside the solve.
                 Row counts that don't divide the mesh are zero-padded
                 and masked, so non-aligned n works on any device count.

``backend="auto"`` (the config default) resolves per platform and block
dtype at trace time: TPU → ``pallas`` for f32/bf16 blocks and ``xla`` for
f64 blocks (Mosaic has no f64), anything else → ``xla``
(``resolve_backend``).

Every executor carries a ``Precision`` policy (``core.precision``): blocks
are materialized in the data dtype, reductions run in ``accum_dtype``, and
the p×p factorizations in ``solve_dtype`` — with sane-core defaults that
leave f64 pipelines bit-identical and give sub-f64 data a widened p×p core
and (below f32) f32 accumulation. The shared ``jittered_cholesky`` floors
its relative jitter per-dtype so the landmark-overlap factorization is
representably PD at any working precision.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import Array
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from ..data.sparse import CsrMatrix
from ..registry import Registry
from .kernels import (Kernel, LinearKernel, PolynomialKernel, RBFKernel)
from .precision import Precision, floored_jitter, storage_floored_jitter

DEFAULT_BLOCK_ROWS = 4096


# ------------------------------------------------------------ mesh plumbing

def shard_map_norep(f, *, mesh: Mesh, in_specs, out_specs):
    """Jitted ``jax.shard_map`` with the varying-manual-axes check off —
    required so the Pallas tile kernels, which have no replication rule,
    can run as the per-shard body. Jitted because a ``shard_map`` called
    eagerly runs its body op by op, compiling every op anew on each
    call (about a hundred programs per sharded fit)."""
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def data_mesh(n_devices: int | None = None, axis: str = "data") -> Mesh:
    """1-D device mesh over the first ``n_devices`` devices (all when None)."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return jax.make_mesh((len(devs),), (axis,), devices=devs,
                         axis_types=(jax.sharding.AxisType.Auto,))


def validated_device_count(
        mesh_shape: int | tuple[int, ...] | None) -> int:
    """Positive device count for an int/tuple/None mesh request, raising —
    never truncating — when it exceeds the host. The ONE validation every
    mesh-count entry point shares (``ShardedOps.n_shards``, the
    ``core.distributed`` wrappers), so they accept identical inputs and
    fail with identical messages."""
    avail = len(jax.devices())
    if mesh_shape is None:
        return avail
    want = (mesh_shape if isinstance(mesh_shape, int)
            else math.prod(mesh_shape))
    if not 1 <= want <= avail:
        raise ValueError(
            f"mesh_shape {mesh_shape!r} needs {want} devices; "
            f"{avail} available (hint: XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N on CPU)")
    return want


# ------------------------------------------------------- shared p×p algebra

def jittered_cholesky(W: Array, jitter: float) -> Array:
    """L with L Lᵀ = 0.5(W + Wᵀ) + jitter′·(tr(W)/p + 1)·I.

    The one jitter convention for every p×p landmark-overlap factorization
    (fast leverage, the distributed shard_map path, and the api solvers all
    share it, so the factor B = C L^{-T} and any landmark-space map L^{-T}v
    built from it stay mutually consistent). Lives here so every backend —
    including the streamed score pass — factors exactly the same matrix.

    jitter′ is the requested jitter floored at the dtype-aware minimum
    (``precision.dtype_jitter_floor``): a relative 1e-10 is representable
    against an O(1) diagonal in f64 but rounds to *nothing* in f32 — the
    jittered matrix is bit-identical to the singular one and the Cholesky
    NaNs. The floor (~sqrt(eps) below f64, ~eps^0.75 ≥ f64) keeps the
    shift visible at the working precision while leaving the f64 default
    of 1e-10 untouched.
    """
    p = W.shape[0]
    jitter = floored_jitter(jitter, W.dtype)
    Wj = 0.5 * (W + W.T) + jitter * (jnp.trace(W) / p + 1.0) * jnp.eye(
        p, dtype=W.dtype)
    return jnp.linalg.cholesky(Wj)


def scores_against_gram(B: Array, G: Array, lam: float, n: int, *,
                        solve_dtype=None) -> Array:
    """Rows of B scored against a precomputed Gram G = BᵀB (eq. 9 split).

    Factors A = ½(G + Gᵀ) + nλI once and reads l̃_i = ‖L⁻¹B_iᵀ‖² off a
    triangular solve. Splitting G out of the row loop is what lets the
    sharded backend psum a global p×p Gram and keep every row local.

    ``solve_dtype`` (a ``Precision.solve_for`` resolution; None = leave the
    path untouched) up-casts the p×p factorization and the triangular
    solve, returning the scores in B's dtype.
    """
    p = B.shape[1]
    out_dtype = B.dtype
    if solve_dtype is not None:
        B, G = B.astype(solve_dtype), G.astype(solve_dtype)
    A = 0.5 * (G + G.T) + n * lam * jnp.eye(p, dtype=B.dtype)
    Lchol = jnp.linalg.cholesky(A)
    V = jax.scipy.linalg.solve_triangular(Lchol, B.T, lower=True)  # (p, n)
    return jnp.sum(V * V, axis=0).astype(out_dtype)


def reference_leverage_scores(B: Array, lam: float, n: int) -> Array:
    """l̃_i = B_i (BᵀB + nλI)^{-1} B_iᵀ — the p-dimensional formula (eq. 9).

    Cholesky + triangular solve; this is the ``xla`` backend's evaluation
    and the numerical reference every other backend is tested against.
    """
    return scores_against_gram(B, B.T @ B, lam, n)


def score_pass_core(Lc: Array, CtC: Array, lam: float, n: int) -> Array:
    """The p×p algebra between the two chunked Theorem-4 passes.

    Given the jittered landmark Cholesky L_c (W ≈ L_c L_cᵀ) and the
    accumulated CᵀC, returns the factor L_a with
    L_a L_aᵀ = A = L_c⁻¹ (CᵀC) L_c⁻ᵀ + nλI — the matrix every per-chunk
    score evaluation solves against. This is the cross-chunk state of the
    whole score pass: O(p²), independent of n. Shared by
    ``StreamingOps.score_pass`` (device-side ``lax.scan``) and the
    out-of-core driver (host-side loop over a ``ChunkSource``), so the
    two paths factor exactly the same matrix.

    A is never formed: its Cholesky comes from the congruent matrix
    M = CᵀC + nλ·L_c L_cᵀ via L_a = L_c⁻¹ chol(M) (lower-triangular with
    positive diagonal, hence THE Cholesky factor of A). Factoring A
    directly NaNs in f32 whenever the landmark set is near-degenerate:
    the L_c⁻¹ congruence amplifies CᵀC's storage rounding by 1/jitter in
    W's near-null directions, pushing eigenvalues of the computed A below
    −nλ. M dodges the amplification — CᵀC is an accumulated Gram (PSD up
    to its accumulation noise) and nλ·L_c L_cᵀ is exactly PSD — but that
    noise is still real: CᵀC can arrive with O(eps_accum·tr(CᵀC))
    indefiniteness that nλ·λ_min(W) cannot cover when W itself is
    near-singular (the BLESS annealer's concentrated late stages hit
    this in f32). When — and only when — the clean factorization NaNs,
    a second one floored at exactly that noise scale takes over: the
    rescue ridge is storage noise, not a model choice, and it perturbs
    nothing in the healthy regime, where the clean factor is used
    unchanged (the backend-parity suites pin that at 1e-5).
    """
    p = Lc.shape[0]
    C2 = CtC.astype(Lc.dtype)
    sym = 0.5 * (C2 + C2.T)
    M = sym + (n * lam) * (Lc @ Lc.T)
    Lm = jnp.linalg.cholesky(M)
    ridge = jnp.finfo(CtC.dtype).eps * (jnp.trace(sym) + 1.0)
    Lm_rescue = jnp.linalg.cholesky(M + ridge * jnp.eye(p, dtype=M.dtype))
    Lm = jnp.where(jnp.any(jnp.isnan(Lm)), Lm_rescue, Lm)
    return jax.scipy.linalg.solve_triangular(Lc, Lm, lower=True)


# ------------------------------------------------------------- the protocol

@dataclasses.dataclass(frozen=True)
class KernelOps:
    """Base executor: a kernel bound to a tiling policy.

    Subclasses override ``cross`` (the one primitive every block derives
    from) and whichever of the derived ops they can do better than the
    generic compositions below. ``streams_score_pass`` advertises a fused
    Theorem-4 ``score_pass`` that avoids materializing (n, p) on any one
    device. ``mesh_shape``/``inner_backend`` are consulted only by the
    ``sharded`` backend; they live on the base so construction stays
    uniform across the registry.

    ``precision`` is the per-stage dtype policy (``core.precision``):
    blocks are materialized in the data dtype, reductions over them run in
    ``accum_dtype``, p×p factorizations in ``solve_dtype``. The default
    policy resolves every stage to None — all casts are skipped and the
    executor behaves bit-identically to the pre-policy code.
    """

    kernel: Kernel
    block_rows: int = DEFAULT_BLOCK_ROWS
    mesh_shape: int | tuple[int, ...] | None = None
    inner_backend: str = "auto"
    precision: Precision = Precision()

    name = "base"
    streams_score_pass = False

    # ------------------------------------------------- precision plumbing

    def _cast_data(self, *arrays: Array) -> tuple[Array, ...]:
        """Arrays in the policy's data (block) dtype; no-op when unset."""
        dd = self.precision.data()
        if dd is None:
            return arrays
        return tuple(a.astype(dd) for a in arrays)

    def _accum(self, dtype):
        """Accumulation dtype for reductions over ``dtype`` (or None)."""
        return self.precision.accum_for(dtype)

    def _solve(self, dtype):
        """p×p factorization dtype for ``dtype`` data (or None)."""
        return self.precision.solve_for(dtype)

    def _gram(self, X: Array, Z: Array) -> Array:
        """One kernel block under the accumulation policy: arithmetic in
        ``accum_dtype``, result materialized back in the inputs' dtype.
        (Inputs are expected to already be in the data dtype.)"""
        acc = self._accum(jnp.result_type(X.dtype, Z.dtype))
        if acc is None:
            return self.kernel.gram(X, Z)
        block = jnp.result_type(X.dtype, Z.dtype)
        return self.kernel.gram(X.astype(acc), Z.astype(acc)).astype(block)

    # ------------------------------------------------------- the protocol

    def cross(self, X_test: Array, Z: Array) -> Array:
        """k(X_test, Z) ∈ R^{m×p} — the one primitive every other block
        derives from; concrete backends must implement it."""
        raise NotImplementedError

    def columns(self, X: Array, idx: Array) -> Array:
        """C = K[:, idx] — only the sampled columns, never forming K."""
        return self.cross(X, X[idx])

    def matvec(self, X: Array, Z: Array, v: Array) -> Array:
        """k(X, Z) @ v — contraction in ``accum_dtype`` when set (the
        quantized serve path: low-precision blocks, widened accumulate)."""
        Kb = self.cross(X, Z)
        acc = self._accum(jnp.result_type(Kb.dtype, v.dtype))
        if acc is None:
            return Kb @ v
        return Kb.astype(acc) @ v.astype(acc)

    def rmatvec(self, X: Array, Z: Array, v: Array) -> Array:
        """k(X, Z)ᵀ @ v."""
        Kb = self.cross(X, Z)
        acc = self._accum(jnp.result_type(Kb.dtype, v.dtype))
        if acc is None:
            return Kb.T @ v
        return Kb.T.astype(acc) @ v.astype(acc)

    def gram_matvec(self, X: Array, Z: Array, v: Array) -> Array:
        """k(X, Z)ᵀ (k(X, Z) @ v) — one CᵀC·v pass, CᵀC never formed.

        The implicit normal-equations operator behind the iterative
        solvers (``SOLVERS["falkon_pcg"]``): the reference path evaluates
        the column block once and contracts twice under the accumulation
        policy; the streaming override fuses both contractions per row
        tile and the sharded one psums per-shard partials, so those
        executors keep a CG iteration free of any O(n·p) intermediate.
        """
        Kb = self.cross(X, Z)
        acc = self._accum(jnp.result_type(Kb.dtype, v.dtype))
        if acc is None:
            return Kb.T @ (Kb @ v)
        Ka = Kb.astype(acc)
        return Ka.T @ (Ka @ v.astype(acc))

    def leverage_scores(self, B: Array, lam: float, n: int) -> Array:
        """l̃_i = B_i (BᵀB + nλI)^{-1} B_iᵀ — the fused eq.-(9) scores;
        the Gram accumulates in ``accum_dtype`` under the policy."""
        acc = self._accum(B.dtype)
        G = B.T @ B if acc is None else (B.T.astype(acc) @ B.astype(acc))
        return self.scores_given_gram(B, G, lam, n)

    def scores_given_gram(self, B: Array, G: Array, lam: float,
                          n: int) -> Array:
        """Rows of B scored against an externally-supplied Gram G = BᵀB.

        The per-shard half of eq. (9): the sharded backend psums the
        global Gram and hands each device its row block through this
        seam, so the inner executor's fused evaluation (e.g. the Pallas
        ``rls_scores`` tile) runs under the shard unchanged.
        """
        return scores_against_gram(B, G, lam, n,
                                   solve_dtype=self._solve(B.dtype))

    # ---------------------------------------- chunked Theorem-4 seam
    # The score pass decomposes into two streamed passes over row chunks
    # with only p×p cross-chunk state (``score_pass_core``). These three
    # methods are that decomposition's per-chunk bodies; the streaming
    # backend scans them device-side, the out-of-core driver
    # (``repro.api.out_of_core``) jits each one and loops host-side over a
    # ``ChunkSource`` — so a fit from disk holds no array ≥ chunk_rows·p.
    # They live on the base so ANY executor (including ``sharded``, which
    # then row-shards each chunk over its mesh) can serve as the chunk
    # engine.

    def score_pass_dtypes(self, dtype) -> tuple:
        """(accum, solve) dtypes the chunked Theorem-4 pass runs in for
        blocks of ``dtype`` — the policy's ``accum_for``/``solve_for``
        resolutions with the block dtype as the "leave untouched"
        fallback, so callers can allocate accumulators without gating on
        None."""
        dt = jnp.dtype(dtype)
        acc, sd = self._accum(dt), self._solve(dt)
        return (dt if acc is None else acc, dt if sd is None else sd)

    def score_pass_chunk_gram(self, xb: Array, mask: Array, Z: Array,
                              accum_dtype) -> Array:
        """One chunk's masked CᵀC contribution (pass 1 of the Theorem-4
        decomposition): k(x, z) ≠ 0 for zero-padded rows, so the mask
        multiplies the block BEFORE the reduction — padded rows are exact
        zeros in every precision. Returns a p×p block in ``accum_dtype``."""
        Cb = (self.cross(xb, Z) * mask[:, None]).astype(accum_dtype)
        return Cb.T @ Cb

    def score_pass_chunk_scores(self, xb: Array, Z: Array, Lc: Array,
                                La: Array) -> tuple[Array, Array]:
        """One chunk's (scores, ‖B_i‖²) rows (pass 2): recompute the
        chunk's C block and read the eq.-(9) scores off two triangular
        solves against the factors from ``score_pass_core``. Peak
        intermediate O(chunk_rows·p)."""
        Cb = self.cross(xb, Z)
        Bt = jax.scipy.linalg.solve_triangular(Lc, Cb.T.astype(Lc.dtype),
                                               lower=True)
        V = jax.scipy.linalg.solve_triangular(La, Bt, lower=True)
        return (jnp.sum(V * V, axis=0).astype(xb.dtype),
                jnp.sum(Bt * Bt, axis=0).astype(xb.dtype))


BACKENDS: Registry[type] = Registry("backend")


# ------------------------------------------------------------ xla reference

@BACKENDS.register("xla")
@dataclasses.dataclass(frozen=True)
class XlaOps(KernelOps):
    """Dense reference: one fused XLA op per block — the only place outside
    ``core/kernels.py`` where ``kernel.gram`` is called directly."""

    name = "xla"

    def cross(self, X_test: Array, Z: Array) -> Array:
        X_test, Z = self._cast_data(X_test, Z)
        return self._gram(X_test, Z)


# ------------------------------------------------------------- pallas tiles

@BACKENDS.register("pallas")
@dataclasses.dataclass(frozen=True)
class PallasOps(KernelOps):
    """Routes blocks to the tiled Pallas TPU kernels (``repro.kernels``).

    On CPU the kernels run in interpret mode (validation); on TPU the same
    call sites lower to real mosaic kernels, so the jitted serving path hits
    the MXU tiles. Kernels without a tiled body (bernoulli) fall back to
    the dense per-block formula.
    """

    # built by ``backend="auto"``: blocks of a dtype no Mosaic tile takes
    # (float64) follow ``resolve_backend``'s dtype rule to the dense XLA
    # formula instead of raising
    auto: bool = False

    name = "pallas"

    def _tiles_take(self, dtype) -> bool:
        """Whether blocks of ``dtype`` run on the Pallas tiles. Off-TPU the
        tiles interpret any dtype. On a TPU float64 has no Mosaic lowering:
        an ``auto`` executor sends it to XLA, an explicit ``pallas`` one
        raises rather than silently running something else."""
        if jax.default_backend() != "tpu" or \
                resolve_backend("auto", dtype) == "pallas":
            return True
        if self.auto:
            return False
        raise ValueError(
            f"backend='pallas' got {jnp.dtype(dtype).name} data on a TPU, "
            f"and the Mosaic tiles have no float64 lowering; set "
            f"Precision.data_dtype='float32' (or 'bfloat16') to run the "
            f"tiles, or use backend='auto' or 'xla' for a float64 fit")

    def _tile_acc(self, *dtypes) -> str | None:
        """Explicit accumulation dtype name for the tile kernels, or None
        to keep their built-in rule (f64 in ⇒ f64 acc, else f32 — already
        the bf16-in / f32-MXU-accumulate contract)."""
        acc = self._accum(jnp.result_type(*dtypes))
        return None if acc is None else acc.name

    def cross(self, X_test: Array, Z: Array) -> Array:
        from ..kernels import ops as kops
        X_test, Z = self._cast_data(X_test, Z)
        if not self._tiles_take(jnp.result_type(X_test.dtype, Z.dtype)):
            return self._gram(X_test, Z)
        acc = self._tile_acc(X_test.dtype, Z.dtype)
        k = self.kernel
        if isinstance(X_test, CsrMatrix):
            # the CSR one-hot MXU tiles (XLA reference off-TPU); kernels
            # without a sparse body (bernoulli) fall through to _gram,
            # whose dispatch raises the descriptive error
            kind = {RBFKernel: "rbf", LinearKernel: "linear",
                    PolynomialKernel: "poly"}.get(type(k))
            if kind is None:
                return self._gram(X_test, Z)
            return kops.sparse_block(
                X_test.data, X_test.indices, X_test.indptr, Z, kind=kind,
                bandwidth=getattr(k, "bandwidth", 1.0),
                degree=getattr(k, "degree", 2),
                scale=getattr(k, "scale", 1.0),
                offset=getattr(k, "offset", 1.0), acc_dtype=acc)
        if isinstance(k, RBFKernel):
            return kops.rbf_block(X_test, Z, bandwidth=k.bandwidth,
                                  acc_dtype=acc)
        if isinstance(k, LinearKernel):
            return kops.linear_block(X_test, Z, acc_dtype=acc)
        if isinstance(k, PolynomialKernel):
            return kops.poly_block(X_test, Z, degree=k.degree,
                                   scale=k.scale, offset=k.offset,
                                   acc_dtype=acc)
        return self._gram(X_test, Z)

    def leverage_scores(self, B: Array, lam: float, n: int) -> Array:
        acc = self._accum(B.dtype)
        G = B.T @ B if acc is None else (B.T.astype(acc) @ B.astype(acc))
        return self.scores_given_gram(B, G, lam, n)

    def scores_given_gram(self, B: Array, G: Array, lam: float,
                          n: int) -> Array:
        # M = (G + nλI)^{-1} once in XLA (O(p³)), then the fused Pallas
        # rowwise B M Bᵀ — one HBM read of B, no n×p intermediate. The
        # inverse runs in solve_dtype when the policy widens it; the tile
        # then reads M at that precision and accumulates per its acc rule.
        from ..kernels import ops as kops
        if not self._tiles_take(B.dtype):
            return KernelOps.scores_given_gram(self, B, G, lam, n)
        p = B.shape[1]
        sd = self._solve(B.dtype)
        wd = B.dtype if sd is None else sd
        A = 0.5 * (G + G.T).astype(wd) + n * lam * jnp.eye(p, dtype=wd)
        c, low = jax.scipy.linalg.cho_factor(A)
        M = jax.scipy.linalg.cho_solve((c, low), jnp.eye(p, dtype=wd))
        return kops.rls_scores(B, M, acc_dtype=self._tile_acc(B.dtype, wd))


# --------------------------------------------------------------- streaming

@BACKENDS.register("streaming")
@dataclasses.dataclass(frozen=True)
class StreamingOps(KernelOps):
    """Row-chunked execution: scans ``block_rows``-sized X tiles so no
    *compute* intermediate larger than O(block_rows · p) is ever live.
    ``matvec``/``rmatvec`` and the Theorem-4 ``score_pass`` are fully
    implicit (C and B never exist); ``columns``/``cross`` still return the
    caller-requested block — chunked in how it is produced, not in size."""

    name = "streaming"
    streams_score_pass = True

    def _row_blocks(self, X: Array) -> tuple[Array, int]:
        """(nb, block_rows, ...) zero-padded view of X plus the pad size."""
        n = X.shape[0]
        br = max(1, min(self.block_rows, n))
        nb = max(1, -(-n // br))
        pad = nb * br - n
        if pad:
            X = jnp.pad(X, ((0, pad),) + ((0, 0),) * (X.ndim - 1))
        return X.reshape((nb, br) + X.shape[1:]), pad

    # CSR inputs skip the dense row re-blocking (jnp.pad/reshape have no
    # CSR analogue): the sparse contraction inside ``_gram`` is already
    # nnz-tiled (kernels.sparse_block), so one direct block evaluation
    # keeps the same O(tile·p) working-set guarantee the row scan gives
    # dense inputs — the derived ``matvec``/``rmatvec``/``gram_matvec``
    # then ride the base compositions over that cross.

    def cross(self, X_test: Array, Z: Array) -> Array:
        X_test, Z = self._cast_data(X_test, Z)
        if isinstance(X_test, CsrMatrix):
            return self._gram(X_test, Z)
        n = X_test.shape[0]
        blocks, _ = self._row_blocks(X_test)
        out = jax.lax.map(lambda xb: self._gram(xb, Z), blocks)
        return out.reshape(-1, Z.shape[0])[:n]

    def matvec(self, X: Array, Z: Array, v: Array) -> Array:
        if isinstance(X, CsrMatrix):
            return KernelOps.matvec(self, X, Z, v)
        X, Z = self._cast_data(X, Z)
        n = X.shape[0]
        blocks, _ = self._row_blocks(X)
        acc = self._accum(jnp.result_type(X.dtype, v.dtype))
        if acc is None:
            body = lambda xb: self._gram(xb, Z) @ v
        else:
            va = v.astype(acc)
            body = lambda xb: self._gram(xb, Z).astype(acc) @ va
        out = jax.lax.map(body, blocks)
        # v may be (p,) or (p, k) (multi-output duals) — keep trailing dims
        return out.reshape((-1,) + out.shape[2:])[:n]

    def rmatvec(self, X: Array, Z: Array, v: Array) -> Array:
        if isinstance(X, CsrMatrix):
            return KernelOps.rmatvec(self, X, Z, v)
        X, Z = self._cast_data(X, Z)
        blocks, pad = self._row_blocks(X)
        if pad:
            v = jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
        vb = v.reshape(blocks.shape[:2] + v.shape[1:])
        acc = self._accum(jnp.result_type(X.dtype, v.dtype))
        acc0_dtype = jnp.result_type(X.dtype, v.dtype) if acc is None else acc

        def step(carry, xv):
            xblk, vblk = xv
            Kb = self._gram(xblk, Z)
            if acc is not None:
                Kb, vblk = Kb.astype(acc), vblk.astype(acc)
            return carry + Kb.T @ vblk, None

        acc0 = jnp.zeros((Z.shape[0],) + v.shape[1:], dtype=acc0_dtype)
        return jax.lax.scan(step, acc0, (blocks, vb))[0]

    def gram_matvec(self, X: Array, Z: Array, v: Array) -> Array:
        # One fused scan: each row tile contributes Kbᵀ(Kb v) to a p-sized
        # accumulator, so live state is O(block_rows·p). Zero-padded tail
        # rows have NONZERO kernel values (k(0, z) ≠ 0 for e.g. RBF), so
        # the inner product is masked before the second contraction.
        if isinstance(X, CsrMatrix):
            return KernelOps.gram_matvec(self, X, Z, v)
        X, Z = self._cast_data(X, Z)
        n = X.shape[0]
        blocks, _ = self._row_blocks(X)
        nb, br = blocks.shape[:2]
        mask = (jnp.arange(nb * br) < n).reshape(nb, br)
        acc = self._accum(jnp.result_type(X.dtype, v.dtype))
        work = jnp.result_type(X.dtype, v.dtype) if acc is None else acc
        va = v.astype(work)
        mshape = (br,) + (1,) * (v.ndim - 1)

        def step(carry, xv):
            xblk, mblk = xv
            Kb = self._gram(xblk, Z).astype(work)
            u = (Kb @ va) * mblk.reshape(mshape).astype(work)
            return carry + Kb.T @ u, None

        out0 = jnp.zeros((Z.shape[0],) + v.shape[1:], dtype=work)
        return jax.lax.scan(step, out0, (blocks, mask))[0]

    def leverage_scores(self, B: Array, lam: float, n: int) -> Array:
        p = B.shape[1]
        blocks, _ = self._row_blocks(B)
        acc = self._accum(B.dtype)
        G0 = jnp.zeros((p, p), dtype=B.dtype if acc is None else acc)

        def step(carry, bb):
            if acc is not None:
                bb = bb.astype(acc)
            return carry + bb.T @ bb, None

        G = jax.lax.scan(step, G0, blocks)[0]
        return self.scores_given_gram(B, G, lam, n)

    def scores_given_gram(self, B: Array, G: Array, lam: float,
                          n: int) -> Array:
        p = B.shape[1]
        sd = self._solve(B.dtype)
        wd = B.dtype if sd is None else sd
        A = 0.5 * (G + G.T).astype(wd) + n * lam * jnp.eye(p, dtype=wd)
        Lchol = jnp.linalg.cholesky(A)
        blocks, _ = self._row_blocks(B)

        def block_scores(bb):
            V = jax.scipy.linalg.solve_triangular(Lchol, bb.T.astype(wd),
                                                  lower=True)
            return jnp.sum(V * V, axis=0).astype(B.dtype)

        return jax.lax.map(block_scores, blocks).reshape(-1)[:B.shape[0]]

    # the chunk-seam bodies run on the already-blocked rows, so they call
    # ``_gram`` directly instead of the base ``cross`` (which would wrap a
    # redundant single-block ``lax.map`` around each chunk)

    def score_pass_chunk_gram(self, xb: Array, mask: Array, Z: Array,
                              accum_dtype) -> Array:
        Cb = (self._gram(xb, Z) * mask[:, None]).astype(accum_dtype)
        return Cb.T @ Cb

    def score_pass_chunk_scores(self, xb: Array, Z: Array, Lc: Array,
                                La: Array) -> tuple[Array, Array]:
        Cb = self._gram(xb, Z)
        Bt = jax.scipy.linalg.solve_triangular(Lc, Cb.T.astype(Lc.dtype),
                                               lower=True)
        V = jax.scipy.linalg.solve_triangular(La, Bt, lower=True)
        return (jnp.sum(V * V, axis=0).astype(xb.dtype),
                jnp.sum(Bt * Bt, axis=0).astype(xb.dtype))

    def score_pass(self, X: Array, idx: Array, lam: float,
                   jitter: float) -> tuple[Array, Array]:
        """Theorem-4 scores in two streamed passes — C and B never exist.

        Pass 1 accumulates CᵀC block-by-block
        (``score_pass_chunk_gram``), giving BᵀB = L⁻¹ (CᵀC) L⁻ᵀ with L
        the jittered Cholesky of the landmark overlap W
        (``score_pass_core``). Pass 2 recomputes each C-block and reads
        off its scores and ‖B_i‖² rows through two triangular solves
        (``score_pass_chunk_scores``). Peak intermediate:
        O(block_rows·p + p²), for any n. The same three seam pieces drive
        the out-of-core fit (``repro.api.out_of_core``), which loops them
        host-side over a ``ChunkSource`` instead of scanning device-side.

        Under a non-default precision policy the CᵀC accumulation runs in
        ``accum_dtype`` and every p×p factorization/solve (both jittered
        Choleskys included) in ``solve_dtype``; the jitter itself is
        floored per-dtype inside ``jittered_cholesky`` either way.

        Returns (scores, row_sq) with row_sq_i = ‖B_i‖² — the quantity the
        recursive sampler's deficit overestimate needs, since B itself is
        never formed.
        """
        (X,) = self._cast_data(X)
        n = X.shape[0]
        Z = X[idx]
        W = self._gram(Z, Z)                           # (p, p) — small
        ad, wd = self.score_pass_dtypes(W.dtype)
        # sub-f32 blocks carry O(eps_storage) rounding that the wide solve
        # can't see — floor the jitter at the storage dtype before upcast
        Lc = jittered_cholesky(W.astype(wd),
                               storage_floored_jitter(jitter, W.dtype))
        if isinstance(X, CsrMatrix):
            # one whole-block pass: the CSR contraction is nnz-tiled
            # inside ``_gram``, so the chunk-seam bodies already run at
            # the streamed working set without dense row re-blocking; an
            # in-memory CsrMatrix has no padded rows, so the gram mask
            # is all-ones
            mask = jnp.ones((n,), W.dtype)
            CtC = self.score_pass_chunk_gram(X, mask, Z, ad)
            La = score_pass_core(Lc, CtC, lam, n)
            return self.score_pass_chunk_scores(X, Z, Lc, La)
        p = Z.shape[0]
        blocks, _ = self._row_blocks(X)
        nb, br = blocks.shape[:2]
        # k(0, z) ≠ 0 for most kernels, so the zero-padded tail rows must be
        # masked out of the CᵀC accumulation (they are simply sliced off in
        # the per-row outputs, but here they would pollute the sum). The
        # mask multiplies the block BEFORE any reduction — padded rows are
        # exact zeros from here on, in every precision.
        mask = (jnp.arange(nb * br) < n).astype(W.dtype).reshape(nb, br)

        def accum(carry, xm):
            xb, mb = xm
            return carry + self.score_pass_chunk_gram(xb, mb, Z, ad), None

        CtC = jax.lax.scan(accum, jnp.zeros((p, p), dtype=ad),
                           (blocks, mask))[0]
        La = score_pass_core(Lc, CtC, lam, n)

        scores, row_sq = jax.lax.map(
            lambda xb: self.score_pass_chunk_scores(xb, Z, Lc, La), blocks)
        return scores.reshape(-1)[:n], row_sq.reshape(-1)[:n]


# ----------------------------------------------------------------- sharded

@BACKENDS.register("sharded")
@dataclasses.dataclass(frozen=True)
class ShardedOps(KernelOps):
    """Mesh-aware SPMD executor: rows sharded over a ``data`` axis.

    X (and any row-aligned vector) is row-sharded over ``mesh_shape``
    devices via ``shard_map``; each device produces its C/B blocks through
    the per-shard *inner* executor (``inner_backend``: xla | pallas |
    streaming — PR 2's tiles compose under the shard untouched). Every
    cross-device collective is p-sized: the fused Theorem-4
    ``score_pass``/``leverage_pass`` psum one p×p Gram BᵀB (plus the
    scalar d_eff), ``rmatvec`` psums a length-p vector — the SPMD
    translation of "never form K". Leading dimensions that don't divide
    the mesh are zero-padded and masked, so non-aligned n works on any
    device count.
    """

    axis_name: str = "data"
    device_mesh: Mesh | None = None   # explicit mesh — overrides mesh_shape

    name = "sharded"
    streams_score_pass = True

    def __post_init__(self) -> None:
        if self.inner_backend == "sharded":
            raise ValueError("sharded backend cannot nest itself: "
                             "inner_backend must be xla|pallas|streaming|auto")

    @property
    def n_shards(self) -> int:
        """Device count on the data axis (``mesh_shape``; None → all)."""
        if self.device_mesh is not None:
            return math.prod(self.device_mesh.shape.values())
        return validated_device_count(self.mesh_shape)

    def mesh(self) -> Mesh:
        """The data mesh: a caller-supplied ``device_mesh`` verbatim
        (preserving its device selection/order), else the first
        ``n_shards`` devices."""
        if self.device_mesh is not None:
            return self.device_mesh
        return data_mesh(self.n_shards, self.axis_name)

    def inner(self) -> KernelOps:
        """The per-shard executor (resolved fresh, like ``auto`` itself);
        carries this executor's precision policy so quantized blocks and
        widened accumulation compose under the shard unchanged."""
        return ops_for(self.kernel, self.inner_backend, self.block_rows,
                       precision=self.precision)

    def _sparse_inner(self) -> KernelOps:
        """The executor CSR inputs ride: ``shard_map`` needs a dense,
        pad-able leading axis that a flat nnz stream does not have, so
        the sharded backend routes sparse blocks through a streaming
        executor carrying the same kernel/tiling/precision — the
        documented "sharded rides the streaming inner path" rule; the
        result is bit-identical to the streaming backend's."""
        return ops_for(self.kernel, "streaming", self.block_rows,
                       precision=self.precision)

    def _shard_rows(self, *arrays: Array) -> list[Array]:
        """Zero-pad each array's leading axis to a multiple of the mesh."""
        d = self.n_shards
        out = []
        for A in arrays:
            pad = -A.shape[0] % d
            if pad:
                A = jnp.pad(A, ((0, pad),) + ((0, 0),) * (A.ndim - 1))
            out.append(A)
        return out

    def cross(self, X_test: Array, Z: Array) -> Array:
        if isinstance(X_test, CsrMatrix):
            return self._sparse_inner().cross(X_test, Z)
        inner, ax = self.inner(), self.axis_name
        (Xp,) = self._shard_rows(X_test)
        fn = shard_map_norep(
            lambda xb, z: inner.cross(xb, z), mesh=self.mesh(),
            in_specs=(P(ax, None), P(None, None)), out_specs=P(ax, None))
        return fn(Xp, Z)[:X_test.shape[0]]

    def matvec(self, X: Array, Z: Array, v: Array) -> Array:
        # v replicated, output row-sharded — no collective at all.
        if isinstance(X, CsrMatrix):
            return self._sparse_inner().matvec(X, Z, v)
        inner, ax = self.inner(), self.axis_name
        (Xp,) = self._shard_rows(X)
        fn = shard_map_norep(
            lambda xb, z, vv: inner.matvec(xb, z, vv), mesh=self.mesh(),
            in_specs=(P(ax, None), P(None, None), P(*(None,) * v.ndim)),
            out_specs=P(ax, *(None,) * (v.ndim - 1)))
        return fn(Xp, Z, v)[:X.shape[0]]

    def rmatvec(self, X: Array, Z: Array, v: Array) -> Array:
        # v rides X's row sharding (zero-padded rows contribute zero);
        # the one collective is the p(-by-k)-sized psum of the partials.
        if isinstance(X, CsrMatrix):
            return self._sparse_inner().rmatvec(X, Z, v)
        inner, ax = self.inner(), self.axis_name
        Xp, vp = self._shard_rows(X, v)
        fn = shard_map_norep(
            lambda xb, z, vb: jax.lax.psum(inner.rmatvec(xb, z, vb), ax),
            mesh=self.mesh(),
            in_specs=(P(ax, None), P(None, None),
                      P(ax, *(None,) * (v.ndim - 1))),
            out_specs=P(*(None,) * v.ndim))
        return fn(Xp, Z, vp)

    def gram_matvec(self, X: Array, Z: Array, v: Array) -> Array:
        # v replicated in, result replicated out; each shard runs the
        # inner executor's fused CᵀC·v on its row block and the one
        # collective is the p(-by-k)-sized psum of the partials. When the
        # row count doesn't divide the mesh, the zero-padded tail rows
        # have nonzero kernel values, so the padded path masks between
        # the two inner contractions instead.
        if isinstance(X, CsrMatrix):
            return self._sparse_inner().gram_matvec(X, Z, v)
        inner, ax = self.inner(), self.axis_name
        (Xp,) = self._shard_rows(X)
        n = X.shape[0]
        vspec = P(*(None,) * v.ndim)
        if Xp.shape[0] == n:
            fn = shard_map_norep(
                lambda xb, z, vv: jax.lax.psum(
                    inner.gram_matvec(xb, z, vv), ax),
                mesh=self.mesh(),
                in_specs=(P(ax, None), P(None, None), vspec),
                out_specs=vspec)
            return fn(Xp, Z, v)
        mask = (jnp.arange(Xp.shape[0]) < n).astype(Xp.dtype)

        def local(xb, z, vv, mb):
            u = inner.matvec(xb, z, vv)
            u = u * mb.reshape((-1,) + (1,) * (vv.ndim - 1)).astype(u.dtype)
            return jax.lax.psum(inner.rmatvec(xb, z, u), ax)

        fn = shard_map_norep(local, mesh=self.mesh(),
                             in_specs=(P(ax, None), P(None, None), vspec,
                                       P(ax)),
                             out_specs=vspec)
        return fn(Xp, Z, v, mask)

    def leverage_scores(self, B: Array, lam: float, n: int) -> Array:
        # G = psum of per-shard BᵀB (the p×p collective); each shard then
        # scores its rows through the inner executor's fused evaluation.
        inner, ax = self.inner(), self.axis_name
        (Bp,) = self._shard_rows(B)

        def local(bb):
            G = jax.lax.psum(bb.T @ bb, ax)
            return inner.scores_given_gram(bb, G, lam, n)

        fn = shard_map_norep(local, mesh=self.mesh(),
                             in_specs=(P(ax, None),), out_specs=P(ax))
        return fn(Bp)[:B.shape[0]]

    def leverage_pass(self, X: Array, landmarks: Array, lam: float,
                      jitter: float) -> tuple[Array, Array, Array]:
        """Sharded §3.5 factor build: (scores, B, d_eff), collectives p×p.

        W = k(Z, Z) and its jittered Cholesky are built on every device
        (replicated, p×p — inside the ``shard_map`` like every other inner
        block, since a Mosaic kernel cannot be partitioned automatically);
        per shard C_blk = k(X_blk, Z) through the inner executor and
        B_blk = C_blk L⁻ᵀ; one psum of B_blkᵀB_blk gives the global Gram
        for eq. (9) plus the scalar d_eff psum. Padded tail rows are
        masked out of the Gram (k(0, z) ≠ 0) and sliced off the outputs —
        the mask multiplies B_blk BEFORE the Gram reduction (and before
        any further transform), so a zero-padded row contributes exact
        zeros in every precision: it can never leak a k(0, z) value, let
        alone a NaN/Inf, into the psum. Under a non-default precision
        policy the Gram accumulates in ``accum_dtype`` and the jittered
        Cholesky runs in ``solve_dtype`` (jitter floored per-dtype either
        way); the inner executor applies the same policy to its blocks.
        """
        if isinstance(X, CsrMatrix):
            raise NotImplementedError(
                "leverage_pass materializes the sharded B factor via "
                "shard_map, which needs dense rows; for CsrMatrix inputs "
                "use score_pass (it rides the streaming inner path)")
        n = X.shape[0]
        inner, ax = self.inner(), self.axis_name
        (X,) = self._cast_data(X)
        (landmarks,) = self._cast_data(landmarks)
        dt = landmarks.dtype
        sd, acc = self._solve(dt), self._accum(dt)
        (Xp,) = self._shard_rows(X)
        mask = (jnp.arange(Xp.shape[0]) < n).astype(dt)

        def local(xb, mb, z):
            W = inner.cross(z, z)
            Lc = jittered_cholesky(W if sd is None else W.astype(sd),
                                   storage_floored_jitter(jitter, dt))
            Cb = inner.cross(xb, z)
            # B rows come back in the block dtype (the factor is O(n·p)
            # state) even when the triangular solve ran at solve precision
            Bb = jax.scipy.linalg.solve_triangular(
                Lc, Cb.T.astype(Lc.dtype), lower=True).T.astype(
                    Cb.dtype) * mb[:, None]
            Bg = Bb if acc is None else Bb.astype(acc)
            G = jax.lax.psum(Bg.T @ Bg, ax)            # the p×p collective
            scores = inner.scores_given_gram(Bb, G, lam, n)
            d_eff = jax.lax.psum(jnp.sum(scores), ax)
            return scores, Bb, d_eff

        fn = shard_map_norep(
            local, mesh=self.mesh(),
            in_specs=(P(ax, None), P(ax), P(None, None)),
            out_specs=(P(ax), P(ax, None), P()))
        scores, B, d_eff = fn(Xp, mask, landmarks)
        return scores[:n], B[:n], d_eff

    def score_pass(self, X: Array, idx: Array, lam: float,
                   jitter: float) -> tuple[Array, Array]:
        """Theorem-4 scores with no (n, p) block on any single device.

        Same contract as the streaming ``score_pass``: returns
        (scores, row_sq) so ``fast_ridge_leverage`` reports ``B=None``
        and the recursive sampler still gets its ‖B_i‖² deficits.
        """
        if isinstance(X, CsrMatrix):
            return self._sparse_inner().score_pass(X, idx, lam, jitter)
        scores, B, _ = self.leverage_pass(X, jnp.take(X, idx, axis=0),
                                          lam, jitter)
        return scores, jnp.sum(B * B, axis=1)


# -------------------------------------------------------------- resolution

def resolve_backend(name: str = "auto", dtype=None) -> str:
    """Registry name for ``name``, resolving ``"auto"`` per platform and
    data dtype.

    ``auto`` → ``pallas`` on TPU (the tiles lower to real Mosaic kernels
    there) for blocks of any dtype the tiles take — f32 and bf16 — and
    ``xla`` for float64 blocks on TPU, because Mosaic has no float64;
    ``xla`` on every other platform (on CPU/GPU the Pallas tiles would run
    in interpret mode, which only exists for validation). ``dtype`` is the
    block (data) dtype when the caller knows it; ``None`` means "not known
    yet", and an ``auto``-built ``pallas`` executor then applies the same
    rule per block (``PallasOps.auto``). Re-evaluated on every call —
    keyed on the *current* ``jax.default_backend()`` — so platform
    simulation in tests is never pinned by a first-call cache.
    """
    if name == "auto":
        if jax.default_backend() != "tpu":
            return "xla"
        f64 = dtype is not None and jnp.dtype(dtype) == jnp.float64
        return "xla" if f64 else "pallas"
    if name not in BACKENDS:
        BACKENDS.get(name)  # raises KeyError listing the available names
    return name


def ops_for(kernel: Kernel, backend: str = "auto",
            block_rows: int = DEFAULT_BLOCK_ROWS, *,
            mesh_shape: int | tuple[int, ...] | None = None,
            inner_backend: str = "auto",
            precision: Precision = Precision()) -> KernelOps:
    """Construct the ``KernelOps`` executor for a kernel + backend name.

    ``mesh_shape``/``inner_backend`` parameterize the ``sharded`` backend
    (data-axis device count and per-shard executor); other backends carry
    them inertly. ``precision`` is the per-stage dtype policy
    (``core.precision.Precision``; the default changes nothing).
    """
    name = resolve_backend(backend)
    extra = {"auto": True} if name == "pallas" and backend == "auto" else {}
    return BACKENDS.get(name)(
        kernel=kernel, block_rows=block_rows, mesh_shape=mesh_shape,
        inner_backend=inner_backend, precision=precision, **extra)


def ops_for_config(config) -> KernelOps:
    """Executor for anything config-shaped (``kernel``/``backend``/
    ``block_rows``/``mesh_shape``/``inner_backend``/``precision``
    attributes; all but ``kernel`` optional for legacy configs)."""
    return ops_for(config.kernel,
                   getattr(config, "backend", "auto"),
                   getattr(config, "block_rows", DEFAULT_BLOCK_ROWS),
                   mesh_shape=getattr(config, "mesh_shape", None),
                   inner_backend=getattr(config, "inner_backend", "auto"),
                   precision=getattr(config, "precision", Precision()))
