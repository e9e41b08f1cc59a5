"""Column samplers behind one call signature (paper §2, §3.4-3.5).

Every sampler is ``(key, kernel, X, config) -> SamplerOutput`` — the
``Sampler`` protocol — replacing the seed repo's mismatched free functions
(``uniform_sampler(key, K_diag, p)`` vs ``rls_sampler(key, scores, p)``).
The sketch size (``config.p``) and score-pass landmark count
(``config.score_pass_p``) live only in the config — one source of truth.
The returned ``SamplerOutput`` carries the ``ColumnSample`` (indices,
distribution, sketch weights — all in the kernel's dtype) plus the
unnormalized score vector that induced the distribution, so
``SketchedKRR.scores()`` works uniformly across samplers.

Key discipline matches the legacy ``build_nystrom``: each sampler splits its
key into (score-pass key, draw key), so a given seed draws the same columns
through either path — the parity tests rely on this.

Every kernel block a sampler touches is produced by the configured
``KernelOps`` backend (``config.backend``/``config.block_rows``, and for
the sharded executor ``config.mesh_shape``/``config.inner_backend``; see
``repro.core.backends``) — no direct dense ``kernel.gram`` here, so with
``backend="sharded"`` the Theorem-4 score pass runs SPMD over the mesh
with one p×p collective.

Registry entries → paper results:
  uniform       p_i = 1/n               Bach's baseline; needs p = O(d_mof).
  diagonal      p_i = K_ii/Tr(K)        Theorem-4 seed distribution.
  rls_exact     p_i ∝ l_i(λε)           Definition 1 oracle (O(n³); small n).
  rls_fast      p_i ∝ l̃_i(λε)           Theorem 4 scores → Theorem 3 draw,
                                        O(n·p_scores²) — the paper pipeline.
  recursive_rls level-refined l̃         Musco-Musco-style bootstrap
                                        (beyond-paper; see core/recursive_rls).
  bless         λ-annealed sequential l̃  BLESS bottom-up schedule (Rudi
                                        et al. 2018; see core/bless) —
                                        O(n·q²·log n) with q ≪ p_scores.
"""
from __future__ import annotations

from typing import NamedTuple, Protocol

import jax
import jax.numpy as jnp
from jax import Array
from jax.profiler import TraceAnnotation

from ..core.backends import ops_for_config
from ..core.bless import bless_leverage
from ..core.kernels import Kernel
from ..core.leverage import fast_ridge_leverage, ridge_leverage_scores
from ..core.nystrom import ColumnSample, draw_columns
from ..core.recursive_rls import recursive_ridge_leverage
from .config import SketchConfig
from .registry import Registry


class SamplerOutput(NamedTuple):
    """What every sampler returns: the Theorem-3 column draw plus the
    unnormalized score vector that induced its distribution."""

    sample: ColumnSample   # columns drawn with replacement + S weights
    scores: Array          # (n,) unnormalized scores behind the distribution


class Sampler(Protocol):
    """Unified sampler signature: all registry entries are callables
    ``(key, kernel, X, config) -> SamplerOutput``; sketch size and
    score-pass landmark count are read off the config."""

    def __call__(self, key: Array, kernel: Kernel, X: Array,
                 config: SketchConfig) -> SamplerOutput: ...


SAMPLERS: Registry[Sampler] = Registry("sampler")


def _finish(key: Array, scores: Array, p: int) -> SamplerOutput:
    with TraceAnnotation("sampler.draw"):
        probs = scores / jnp.sum(scores)
        return SamplerOutput(draw_columns(key, probs, p), scores)


@SAMPLERS.register("uniform")
def uniform(key: Array, kernel: Kernel, X: Array,
            config: SketchConfig) -> SamplerOutput:
    """Bach's vanilla Nyström baseline: p_i = 1/n (needs p = O(d_mof))."""
    _, ks = jax.random.split(key)
    diag = kernel.diag(X)
    return _finish(ks, jnp.ones_like(diag), config.p)


@SAMPLERS.register("diagonal")
def diagonal(key: Array, kernel: Kernel, X: Array,
             config: SketchConfig) -> SamplerOutput:
    """Squared-length sampling p_i = K_ii/Tr(K) — the Theorem-4 seed
    distribution."""
    _, ks = jax.random.split(key)
    return _finish(ks, kernel.diag(X), config.p)


@SAMPLERS.register("rls_exact")
def rls_exact(key: Array, kernel: Kernel, X: Array,
              config: SketchConfig) -> SamplerOutput:
    """Definition-1 oracle: p_i ∝ exact l_i(λε) via the full n×n Gram —
    O(n³), diagnostics/small n only."""
    _, ks = jax.random.split(key)
    K = ops_for_config(config).cross(X, X)  # oracle: full K (small n only)
    scores = ridge_leverage_scores(K, config.lam * config.eps)
    return _finish(ks, scores, config.p)


@SAMPLERS.register("rls_fast")
def rls_fast(key: Array, kernel: Kernel, X: Array,
             config: SketchConfig) -> SamplerOutput:
    """The paper pipeline: Theorem-4 fast scores at λε from
    ``config.score_pass_p`` landmarks, then the Theorem-3 leverage draw
    of ``config.p`` columns — O(n·p_scores²)."""
    kd, ks = jax.random.split(key)
    with TraceAnnotation("sampler.score_pass"):
        fast = fast_ridge_leverage(kernel, X, config.lam * config.eps,
                                   min(config.score_pass_p, X.shape[0]), kd,
                                   jitter=config.jitter,
                                   ops=ops_for_config(config))
    return _finish(ks, fast.scores, config.p)


@SAMPLERS.register("bless")
def bless(key: Array, kernel: Kernel, X: Array,
          config: SketchConfig) -> SamplerOutput:
    """BLESS sequential leverage sampling (Rudi et al. 2018): λ annealed
    geometrically from Tr(K)/n down to λε, each stage scoring against a
    small overestimate-drawn dictionary (``bless_stages`` /
    ``bless_oversample``; per-stage dictionaries capped at ``p_scores``)
    — O(n·q²·log n) with q ≪ p_scores; see ``core/bless``."""
    kd, ks = jax.random.split(key)
    res = bless_leverage(kernel, X, config.lam * config.eps, kd,
                         stages=config.bless_stages,
                         oversample=config.bless_oversample,
                         q_max=min(config.score_pass_p, X.shape[0]),
                         jitter=config.jitter,
                         ops=ops_for_config(config))
    return _finish(ks, res.scores, config.p)


@SAMPLERS.register("recursive_rls")
def recursive_rls(key: Array, kernel: Kernel, X: Array,
                  config: SketchConfig) -> SamplerOutput:
    """Level-wise refined leverage sampling (beyond-paper, Musco & Musco
    2017 style; see ``core/recursive_rls``)."""
    kd, ks = jax.random.split(key)
    res = recursive_ridge_leverage(kernel, X, config.lam * config.eps,
                                   min(config.score_pass_p, X.shape[0]), kd,
                                   n_levels=config.rls_levels,
                                   ops=ops_for_config(config))
    return _finish(ks, res.scores, config.p)
