"""Reproduce paper Figure 1: leverage scores on the asymmetric synthetic
(left panel) and risk vs p per sampling method (right panel) — ASCII plots.

    PYTHONPATH=src python examples/paper_fig1.py

The right panel sweeps the sampler registry of the unified API: one
``SketchConfig`` per (sampler, p, seed), every fit through ``SketchedKRR``.
"""
import sys
sys.path.insert(0, "src")

import jax
jax.config.update("jax_enable_x64", True)
from repro.compile_cache import enable_compile_cache
enable_compile_cache()
import jax.numpy as jnp
import numpy as np

from repro.api import SAMPLERS, SamplerOutput, SketchConfig, SketchedKRR
from repro.core import (BernoulliKernel, draw_columns, effective_dimension,
                        gram_matrix, ridge_leverage_scores, risk_exact)
from repro.data import bernoulli_synthetic

n, lam = 500, 1e-6
data = bernoulli_synthetic(n, seed=0, b=2)
x = data["x"][:, 0]
X = jnp.asarray(data["x"])
f_star = jnp.asarray(data["f_star"])
ker = BernoulliKernel(b=2)
K = gram_matrix(ker, X)
scores = np.asarray(ridge_leverage_scores(K, lam))
d_eff = float(effective_dimension(K, lam))

# ---- left panel: scores vs position (binned ASCII)
print("λ-ridge leverage scores vs x (data dense at borders, sparse center)")
bins = np.linspace(0, 1, 21)
for i in range(20):
    m = (x >= bins[i]) & (x < bins[i + 1])
    if m.sum() == 0:
        print(f"  [{bins[i]:.2f},{bins[i+1]:.2f})  (no points)")
        continue
    s = scores[m].mean()
    bar = "#" * int(s / scores.max() * 50)
    print(f"  [{bins[i]:.2f},{bins[i+1]:.2f})  n={m.sum():3d}  {s:.4f} {bar}")
print(f"  d_eff = {d_eff:.1f}   (n = {n})\n")

# ---- right panel: risk vs p per sampler (all through SketchedKRR)
# rls_exact would rebuild the n×n Gram inside each of the 20 sweep fits; we
# already hold K, so register a sampler closed over the once-computed λε
# scores (the registry's extension point) — same key discipline as
# rls_exact, so each seed draws the same columns.
eps = SketchConfig(kernel=ker, p=1, lam=lam).eps
scores_eps = ridge_leverage_scores(K, lam * eps)


@SAMPLERS.register("rls_exact_cached")
def _rls_exact_cached(key, kernel, X_, config):
    _, ks = jax.random.split(key)
    probs = scores_eps / jnp.sum(scores_eps)
    return SamplerOutput(draw_columns(ks, probs, config.p), scores_eps)


r_exact = float(risk_exact(K, f_star, lam, data["noise"]).risk)
print(f"MSE risk ratio vs p (exact risk = {r_exact:.2e})")
print(f"{'p':>5s} | {'uniform':>9s} | {'rls_fast':>9s} | {'rls_exact':>9s}")
for p in [int(d_eff), int(2 * d_eff), int(4 * d_eff), int(8 * d_eff)]:
    row = [f"{p:5d}"]
    for sampler in ["uniform", "rls_fast", "rls_exact_cached"]:
        vals = []
        for s in range(5):
            cfg = SketchConfig(kernel=ker, p=p, lam=lam, sampler=sampler,
                               solver="nystrom", seed=s)
            model = SketchedKRR(cfg).fit(X, jnp.asarray(data["y"]))
            vals.append(float(model.risk(f_star, data["noise"]).risk))
        row.append(f"{np.mean(vals) / r_exact:9.3f}")
    print(" | ".join(row))
print("\n(leverage sampling reaches ratio ≈ 1 at p ≈ 2·d_eff; uniform "
      "needs far more — the paper's Fig. 1 right panel)")
