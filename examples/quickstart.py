"""Quickstart: fast ridge-leverage Nyström KRR through the unified API.

    PYTHONPATH=src python examples/quickstart.py

Everything goes through ``repro.api.SketchedKRR`` — one configurable
estimator over the sampler/solver registries (see ``repro/api/__init__.py``
for the registry ↔ theorem map):

1. builds a nonlinear regression problem,
2. fits ``SketchedKRR`` with the paper pipeline — ``sampler="rls_fast"``
   (Thm-4 O(np²) scores, then the Thm-3 leverage draw) and
   ``solver="nystrom"`` (Woodbury through the sketch),
3. reads the fast d_eff estimate off ``model.scores()``,
4. compares closed-form risk (eq. 4) against exact KRR (``solver="exact"``),
5. serves out-of-sample predictions through the jitted batched path.
"""
import sys
sys.path.insert(0, "src")

import jax
jax.config.update("jax_enable_x64", True)
from repro.compile_cache import enable_compile_cache
enable_compile_cache()
import jax.numpy as jnp

from repro.api import SketchConfig, SketchedKRR
from repro.core import (RBFKernel, effective_dimension, gram_matrix,
                        max_degrees_of_freedom)
from repro.data import pumadyn_like

data = pumadyn_like(n=2000, seed=0, noise=0.2)
X = jnp.asarray(data["x"])
f_star = jnp.asarray(data["f_star"])
y = jnp.asarray(data["y"])
ker = RBFKernel(bandwidth=float(jnp.sqrt(X.shape[1])))
lam = 1e-3

# -- exact reference (O(n³); only for comparison)
K = gram_matrix(ker, X)
d_eff = float(effective_dimension(K, lam))
d_mof = float(max_degrees_of_freedom(K, lam))
print(f"n=2000  d_eff={d_eff:.1f}  d_mof={d_mof:.1f}  "
      f"(uniform Nyström would need ~d_mof columns; we use ~2·d_eff)")

# -- the paper's pipeline, one estimator object
p = int(2 * d_eff) + 1
config = SketchConfig(kernel=ker, p=p, lam=lam, sampler="rls_fast",
                      solver="nystrom", seed=0)
model = SketchedKRR(config).fit(X, y)
print(f"fast RLS: d_eff estimate {float(jnp.sum(model.scores())):.1f} "
      f"(exact {d_eff:.1f}), kernel evals ~ n·p = {2000 * p:,}")

exact = SketchedKRR(config.replace(solver="exact")).fit(X, y)

r_exact = exact.risk(f_star, data["noise"])
r_nys = model.risk(f_star, data["noise"])
print(f"risk(exact KRR)   = {float(r_exact.risk):.6f}")
print(f"risk(Nyström-RLS) = {float(r_nys.risk):.6f}  "
      f"ratio = {float(r_nys.risk / r_exact.risk):.3f}  (p={p})")

# -- serving path: jit-compiled fixed-batch predict (pads the tail batch)
y_hat = model.predict_batched(X[:300], batch_size=128)
print(f"batched predict: {y_hat.shape[0]} points, "
      f"train-MSE {float(jnp.mean((y_hat - f_star[:300])**2)):.4f}")
